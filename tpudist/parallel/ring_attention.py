"""Ring attention: sequence/context parallelism over a mesh axis.

No reference equivalent — the reference's workload is fixed-size image
classification (SURVEY.md §5 "long-context: absent entirely") — but
long-context sequence parallelism is a first-class capability of this
framework. Design (blockwise/ring attention, cf. Liu et al. ring attention /
flash-attention online softmax):

- the sequence dimension is sharded over a mesh axis (``seq``): each device
  holds a [B, T/n, H, D] slice of Q, K, V;
- K/V blocks rotate around the ring with ``lax.ppermute`` (ICI
  neighbor-to-neighbor transfers — the cheapest collective on a TPU torus)
  while Q stays resident;
- each step does a blockwise attention update with the numerically-stable
  online softmax (running max ``m``, normalizer ``l``, unnormalized output
  ``o``), in fp32 accumulation regardless of input dtype;
- XLA overlaps the ppermute with the block matmuls (latency hiding), so the
  ring costs ~one neighbor hop per step instead of an all-gather of the whole
  sequence: peak memory per device is O(T/n) instead of O(T).

``ring_attention`` is the SPMD (inside-shard_map) form; ``attention`` is the
single-device reference used by tests and by models when no seq axis exists.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpudist.obs import scopes

NEG_INF = -1e30


def block_diffusion_mask(t: int, noisy: int, block: int):
    """The mask of training by diffusion over blocks, [t, t] bool (numpy): a
    self-attention row whose first ``noisy`` positions are a noised copy of
    a row and whose other ``t - noisy`` are the clean row itself (``t = 2 *
    noisy`` in training; ``noisy = 0`` is the clean row alone, as generation
    reads it). With ``n(i)`` the block (``block`` positions) of a position
    within its copy, query ``i`` sees key ``j`` where

    - both noisy: ``n(j) == n(i)`` (its own block, both ways);
    - ``i`` noisy, ``j`` clean: ``n(j) < n(i)`` (the clean blocks before);
    - both clean: ``n(j) <= n(i)`` (causal by blocks);
    - ``i`` clean, ``j`` noisy: never."""
    at = np.arange(t)
    clean = at >= noisy
    n = (at - np.where(clean, noisy, 0)) // block
    q_clean, k_clean, n_q, n_k = (clean[:, None], clean[None, :],
                                  n[:, None], n[None, :])
    return np.where(q_clean, k_clean & (n_k <= n_q),
                    np.where(k_clean, n_k < n_q, n_k == n_q))


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = False, window: Optional[int] = None,
              block_diffusion: Optional[tuple] = None) -> jax.Array:
    """Plain softmax attention; fp32 softmax. ``q`` [B, T, H, D]; ``k`` and
    ``v`` [B, Tk, Hkv, D], where ``Hkv`` divides ``H``: query head ``j``
    reads key-value head ``j // (H // Hkv)`` (grouped-query attention).
    ``window`` (with ``causal``) keeps, of the keys a query may see, the
    nearest ``window``. ``block_diffusion = (L, block)`` states the mask of
    training by diffusion over blocks instead (``block_diffusion_mask``: a
    self-attention whose first ``L`` positions are the noised copy).
    The scores are whole, [B, H, T, Tk] float32: the
    Pallas kernel (``ops/pallas/flash_attention.py``) takes the same
    arguments where they do not fit."""
    if window is not None and not causal:
        raise ValueError("a window is the nearest keys of a causal mask: "
                         "pass causal=True with window")
    if block_diffusion is not None and (causal or q.shape[1] != k.shape[1]):
        raise ValueError("block_diffusion states the whole mask of a "
                         "self-attention: no causal, no window, one length")
    d = q.shape[-1]
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    # The three stages carry scopes (labels only): a trace then says which of
    # an encoder block's fusions is which.
    with jax.named_scope(scopes.ATTN_SCORES):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(d).astype(jnp.float32)
        if causal:
            tq, tk = s.shape[-2], s.shape[-1]
            mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
            if window is not None:
                mask &= ~jnp.tril(jnp.ones((tq, tk), bool),
                                  k=tk - tq - window)
            s = jnp.where(mask, s, NEG_INF)
        if block_diffusion is not None:
            s = jnp.where(block_diffusion_mask(s.shape[-1], *block_diffusion),
                          s, NEG_INF)
    with jax.named_scope(scopes.ATTN_SOFTMAX):
        p = jax.nn.softmax(s, axis=-1)
    with jax.named_scope(scopes.ATTN_VALUES):
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def split_qkv(qkv: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q, k, v [B, T, H, D] out of a head-major fused projection
    [B, T, H, 3, D]."""
    return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]


def qkv_attention(qkv: jax.Array, causal: bool = False,
                  flash: bool = False) -> jax.Array:
    """Self-attention on a fused projection [B, T, H, 3, D] -> [B, T, H, D]:
    the two branches ``MultiHeadAttention`` can take off the sequence-
    parallel path, and so the two functions ``attention_dispatch``'s probe
    times. ``flash`` runs the Pallas kernel (imported only then), which
    reads the projection in place where its shape allows and goes through
    ``split_qkv`` where not; otherwise slices and the XLA ``attention``."""
    if flash:
        # _spmd: under the GSPMD/TP path (ambient mesh via set_mesh) the
        # kernel runs in a nested manual region per batch/head shard;
        # everywhere else it is the plain kernel.
        from tpudist.ops.pallas import flash_attention_spmd
        with jax.named_scope(scopes.ATTN_FUSED):
            return flash_attention_spmd(qkv, causal=causal)
    return attention(*split_qkv(qkv), causal=causal)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = False) -> jax.Array:
    """Sequence-parallel attention over the ``axis_name`` ring.

    Call inside ``shard_map`` with Q/K/V sharded on the sequence dim:
    per-device shapes [B, T_local, H, D]. Returns the local [B, T_local, H, D]
    output slice. ``causal`` masks by GLOBAL position (shard i holds positions
    [i*T_local, (i+1)*T_local)).
    """
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

    q32 = q.astype(jnp.float32)
    o = jnp.zeros((b, h, t_local, d), jnp.float32)
    l = jnp.zeros((b, h, t_local), jnp.float32)
    m = jnp.full((b, h, t_local), NEG_INF, jnp.float32)
    q_pos = my_idx * t_local + jnp.arange(t_local)            # global Q positions

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def body(i, carry):
        o, l, m, k_blk, v_blk = carry
        # After i hops, we hold the K/V block originally on shard (my_idx - i).
        src = (my_idx - i) % axis_size
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]           # [Tq, Tk]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)                            # rescale old acc
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return o_new, l_new, m_new, k_next, v_next

    o, l, m, _, _ = lax.fori_loop(0, axis_size, body, (o, l, m, k, v))
    # Rows with no visible keys (fully masked) have l == 0; output 0 for them.
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def make_ring_attention(mesh, seq_axis: str = "seq", causal: bool = False):
    """Wrap ``ring_attention`` in shard_map for direct use on global arrays
    sharded [B, T@seq, H, D]."""
    from jax.sharding import PartitionSpec as P
    fn = partial(ring_attention, axis_name=seq_axis, causal=causal)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, seq_axis), P(None, seq_axis), P(None, seq_axis)),
        out_specs=P(None, seq_axis),
        check_vma=False))
