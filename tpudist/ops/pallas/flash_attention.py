"""Pallas TPU flash attention: fused blockwise softmax-attention kernel.

No reference equivalent — the reference has no attention at all (SURVEY.md §5
"long-context: absent entirely") and delegates every fused kernel to
cudnn/ATen (SURVEY.md §2.3). This is the framework's hand-written hot-op
path: where the reference leans on closed CUDA kernels, we lean on Pallas.

Two schedules: the streaming one described first (``flash_attention``, split
q / k / v of any length), and the whole-sequence one (its own section at the
end of the file) for self-attention whose scores fit one VMEM block, which
reads the fused QKV projection in place. ``flash_attention_qkv`` chooses
between them from the static shape (``schedule_for``). docs/ATTENTION.md
sets them side by side.

Streaming forward (flash-attention-2 schedule mapped onto the TPU memory
hierarchy):

- grid = (batch, heads, q_blocks, k_blocks), k innermost and marked
  "arbitrary" (sequential) so the running-softmax state carried in VMEM
  scratch is valid across k steps; batch/head/q are "parallel".
- Q stays resident in VMEM for all k steps of a q block; K/V blocks stream
  HBM→VMEM via the BlockSpec pipeline (Pallas double-buffers automatically).
- online softmax in fp32: running max ``m`` and normalizer ``l`` live in
  (block_q, 128) VMEM scratch (lane-broadcast — TPU vregs are 8×128, a
  (bq, 1) column would occupy a full vreg anyway), the unnormalized
  accumulator ``acc`` in (block_q, head_dim) fp32 scratch.
- the two matmuls (S = QKᵀ, O += P·V) hit the MXU in the input dtype
  (bf16 under the AMP policy) with fp32 accumulation; masking/exp/rescale
  fuse into the VPU between them.
- the softmax temperature is folded into Q once on the way in (one XLA
  elementwise pass) instead of rescaling every (bq, bk) score tile on the
  VPU — S = (scale·Q)Kᵀ is already scaled.
- masking is by GLOBAL position: causal (rows ≥ cols), a window (of those,
  the nearest ``window`` keys) and key-validity
  (cols < true key length, so sequence lengths that aren't block multiples —
  ViT's 197 tokens — are padded then exactly masked). The mask is built
  ONLY under configurations that statically need one (causal, or a key
  length that isn't a block multiple) — an exact-tiling non-causal call
  (the 2k-token bench shape) runs a mask-free VPU path. The k dimension of
  the grid counts steps inside a q block's band (``_Band``): with a window
  it is as long as the band is wide, not as the sequence; a step past the
  band's end (above the diagonal) is skipped with ``pl.when`` and fetches
  nothing, its block index staying where it was.
- grouped-query attention: with fewer key-value heads than query heads, a
  query head's index map reads head ``h // group`` of K and V; nothing is
  repeated in HBM.

Streaming backward (the two-pass schedule):

FlashAttention-2's core lesson is that the backward is where naive tiling
drowns: it must be two dedicated passes with the right grid parallelism,
each recomputing probabilities from the forward's saved per-row logsumexp —
never one recompute-everything loop and never an O(T²) tensor.

- **dKV pass**: grid (batch, kv_heads, k_blocks, group, q_blocks), the
  last two sequential — each program owns one (block_k, d) dK/dV tile in
  fp32 VMEM scratch and streams past it the Q/dO blocks of every query head
  that reads this key-value head (the group's sum is taken in VMEM). dK needs no epilogue scale:
  contracting dS (unscaled) against the pre-scaled Q IS the scaled dK.
- **dQ pass**: grid (batch, heads, q_blocks, k_blocks), k innermost
  sequential — each program owns one (block_q, d) dQ tile and streams K/V
  blocks; the temperature is applied once per tile in the epilogue.
- both reuse the forward's saved logsumexp and the precomputed
  ``delta = rowsum(dO ∘ O)`` (an XLA-fused elementwise+reduce outside the
  kernels) instead of rematerializing the softmax normalization per tile,
  so each pass is exactly two MXU matmuls of recompute (S and dP) plus its
  two gradient matmuls.
- accumulators are fp32 over bf16 MXU operands; block sizes default to
  128×128 (a whole MXU tile per matmul, (8, 128)-aligned) and the backward
  blocks are independently tunable (``block_q_bwd``/``block_k_bwd``) from
  the forward's, since the dKV pass wants its resident tile on the KV dim
  while the forward wants it on Q.
- zero-padded Q rows cancel exactly (their dO and delta rows are zero), so
  only key-padding and causality ever generate a mask — the same static
  specialization as the forward.

Whether this kernel actually beats XLA attention *in training* on a real
chip is decided by measurement, not by this docstring: the dispatch layer
(``tpudist/ops/attention_dispatch``) A/Bs both backends per shape and
caches the winner per device kind. ``KERNEL_REV`` below invalidates those
cached verdicts whenever the kernel changes.

Falls back to interpreter mode off-TPU so CPU tests exercise the same kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128

# Bumped whenever kernel math/scheduling changes: attention_dispatch keys its
# cached flash-vs-XLA verdicts on this, so a rebuilt kernel re-measures
# instead of inheriting the old kernel's win/loss record.
#   rev 2: two-pass backward rebuilt — scale folded into Q, static mask
#          specialization, independent backward block sizes.
#   rev 3: whole-sequence schedule for sequences whose scores fit one VMEM
#          block, reading the fused QKV projection in place.
#   rev 4: the streaming schedule takes a window and fewer key-value heads
#          than query heads, walks only the block pairs inside the band, and
#          states its cost; blocks of 1,024 past 1,024 positions.
KERNEL_REV = 4

# the streaming forward's results, as ``jax.ad_checkpoint`` names them
SAVED_BY_NAME = ("flash_attention_out", "flash_attention_lse")

WHOLE_SEQ = "whole_seq"
STREAMING = "streaming"

# What one program of the whole-sequence schedule may hold in VMEM: three
# quarters of the 16 MiB a kernel is given by default, the rest left to the
# compiler's own temporaries. Measured at its edge on a v5e (12 x 64 bf16,
# docs/ATTENTION.md): 640 tokens, the longest it admits, compile and run at
# a seventh of the streaming kernels' time, full and causal; programs past
# it the chip's compiler refuses for VMEM.
_VMEM_BUDGET = 12 * 2**20


def _min(a, b):
    """min of block indices that are Python ints (the static cost count) or
    traced (inside a kernel or an index map)."""
    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _max(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


class _Band:
    """Which (q block, k block) pairs of a streaming call hold a score the
    mask allows, as static numbers and as functions of a block index.

    Query row ``i`` sees key ``j`` where ``j <= i + offset`` (causal;
    ``offset = k_len - q_len``, the XLA ``attention``'s convention) and, with
    a window, ``i + offset - j < window``. A q block then needs the k blocks
    ``k_lo(iq) .. k_hi(iq)`` and a k block the q blocks ``q_lo(ik) ..
    q_hi(ik)``. The kernels' innermost grid dimension counts steps from the
    low end (``steps_k`` / ``steps_q`` of them, the most any block needs: with
    a window far fewer than there are blocks); a step past the high end runs
    nothing, and its block index stays at the high end, so the pipeline
    fetches nothing for it either."""

    def __init__(self, *, causal, window, block_q, block_k, q_len, k_len,
                 nq, nk):
        self.causal, self.window = causal, window
        self.bq, self.bk, self.nq, self.nk = block_q, block_k, nq, nk
        self.offset = k_len - q_len
        self.steps_k, self.steps_q = nk, nq
        if window is not None:
            self.steps_k = min(nk, (block_q + window - 2) // block_k + 2)
            self.steps_q = min(nq, (block_k + window - 2) // block_q + 2)

    # floor division rounds down for negative numbers too (Python and jnp
    # alike), so a block with nothing to see gets a high end below its low

    def k_lo(self, iq):
        if self.window is None:
            return 0
        return _max(iq * self.bq + self.offset - self.window + 1,
                    0) // self.bk

    def k_hi(self, iq):
        if not self.causal:
            return self.nk - 1
        return _min((iq * self.bq + self.bq - 1 + self.offset) // self.bk,
                    self.nk - 1)

    def q_lo(self, ik):
        if not self.causal:
            return 0
        return _max(ik * self.bk - self.offset, 0) // self.bq

    def q_hi(self, ik):
        if self.window is None:
            return self.nq - 1
        return _min((ik * self.bk + self.bk + self.window - 2
                     - self.offset) // self.bq, self.nq - 1)

    def k_block(self, iq, step):
        """(k block of this step, whether it runs, the block to fetch)."""
        kb, hi = self.k_lo(iq) + step, self.k_hi(iq)
        return kb, kb <= hi, jnp.clip(jnp.minimum(kb, hi), 0, self.nk - 1)

    def q_block(self, ik, step):
        qb, hi = self.q_lo(ik) + step, self.q_hi(ik)
        return qb, qb <= hi, jnp.clip(jnp.minimum(qb, hi), 0, self.nq - 1)

    def pairs(self) -> int:
        """Block pairs that run (static): what a call's cost is counted
        from, so that a banded call does not claim the square's work."""
        return sum(max(0, self.k_hi(iq) - self.k_lo(iq) + 1)
                   for iq in range(self.nq))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, band: _Band, q_len: int, k_len: int, mask_k: bool):
    iq = pl.program_id(2)
    step = pl.program_id(3)
    # the k block this step holds; blocks with no unmasked column (above the
    # diagonal, left of the window) are never reached or not run
    ik, run, _ = band.k_block(iq, step)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                                     # (bq, d), scaled
        k = k_ref[0, 0]                                     # (bk, d)
        v = v_ref[0, 0]                                     # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk) f32

        # Mask only under configs that statically need one (mask_k: the key
        # length isn't a block multiple). Padded q ROWS need none: they are
        # dropped on the way out, and their lse guard below keeps them 0.
        s, valid = _masked_scores(s, iq, ik, causal=band.causal,
                                  block_q=band.bq, block_k=band.bk,
                                  q_len=q_len, k_len=k_len, mask_k=mask_k,
                                  window=band.window)

        m_prev = m_scr[:, :1]                               # (bq, 1)
        l_prev = l_scr[:, :1]
        m_curr = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)                             # (bq, bk)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)    # exp(-1e30-m)≈0 anyway
        l_next = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, d) f32
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(step == band.steps_k - 1)
    def _finish():
        l = l_scr[:, :1]
        m = m_scr[:, :1]
        # Fully-masked rows (padded q rows, dropped on the way out): emit 0,
        # not NaN.
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)
        # Per-row logsumexp, saved for the backward recompute. Stored with a
        # trailing singleton dim, (B, H, Tq, 1): Mosaic requires the last two
        # block dims be (multiple-of-8, multiple-of-128-or-full-dim) — a
        # rank-3 (1, 1, block_q) block puts the size-1 head slice in the
        # sublane position and fails to lower on real TPU hardware.
        lse_ref[0, 0] = m + jnp.log(l)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _default_block(t: int) -> int:
    """128 (one MXU tile) up to 1,024 positions, as every shape before the
    long ones ran; 1,024 beyond, where a step's fixed cost (about a third of
    a microsecond) would otherwise rival a small tile's products. Measured
    on a v5e at two sequences of 8,192, 32 heads over 4 of 128, forward +
    backward, ms (docs/ATTENTION.md): blocks of 256 / 512 / 1,024 take 116.1
    / 55.3 / 39.2 full and 36.2 / 22.5 / 20.7 under a window of 1,024."""
    return 128 if t <= 1024 else 1024


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "block_q_bwd", "block_k_bwd",
    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, window: int | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    block_q_bwd: int | None = None,
                    block_k_bwd: int | None = None,
                    interpret: bool | None = None):
    """Fused attention on split operands, the streaming schedule. ``q`` is
    [B, T, H, D], ``k`` and ``v`` [B, Tk, Hkv, D] (sequence-major, matching
    ``tpudist.parallel.ring_attention.attention``); returns [B, T, H, D].

    ``Hkv`` divides ``H``: query head ``j`` reads key-value head ``j // (H //
    Hkv)`` (grouped-query attention; the kernels index the shared head, and
    the backward sums dK / dV over the group in VMEM). ``window`` (static,
    with ``causal``) keeps, of the keys a query may see, the nearest
    ``window``; blocks wholly outside the band are neither run nor fetched
    (``_Band``).

    Numerics: fp32 online softmax, MXU matmuls in the input dtype with fp32
    accumulation — same contract as the pure-XLA ``attention`` it replaces.

    Differentiable: the backward is flash too — two dedicated Pallas passes
    (a dKV pass parallel over KV blocks, a dQ pass parallel over Q blocks)
    recompute the probabilities blockwise from the saved per-row logsumexp
    and the precomputed ``delta = rowsum(dO ∘ O)``; no O(T²) tensor is ever
    materialized. ``block_q_bwd``/``block_k_bwd`` tune the backward blocks
    independently of the forward's (None = same as forward; the forward's
    default is ``_default_block`` of the length).

    A caller that holds the fused projection calls ``flash_attention_qkv``:
    that entry picks the schedule from the shape and comes here only where
    the sequence does not fit one block.
    """
    if window is not None and not causal:
        raise ValueError("a window is the nearest keys of a causal mask: "
                         "pass causal=True with window")
    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise ValueError(f"{q.shape[2]} query heads cannot share "
                         f"{k.shape[2]} key-value heads (k {k.shape}, "
                         f"v {v.shape})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = block_q or _default_block(q.shape[1])
    block_k = block_k or _default_block(k.shape[1])
    return _flash_vjp(q, k, v, causal, window, block_q, block_k,
                      block_q_bwd or block_q, block_k_bwd or block_k,
                      interpret)


def flash_attention_spmd(qkv: jax.Array, causal: bool = False, **kw):
    """``flash_attention_qkv`` that composes with the GSPMD (jit + sharding
    rules) path. ``qkv`` is the fused projection
    [B, T, H, 3, D]; its head axis rides 'model', its batch 'data'.

    ``pallas_call`` has no SPMD partitioning rule, so inside a partitioned
    jit XLA would all-gather the projection and replicate attention on every
    device (the r4 limitation that forced ``--flash off`` under TP). But the
    kernel needs no cross-shard math for batch or head shardings — TP shards
    whole heads by construction (``tensor_parallel.VIT_RULES`` column-shards
    the head-major in_proj) — so under an ambient mesh with Auto
    'data'/'model' axes this wraps the kernel in a nested full-manual
    ``shard_map``: each shard runs the kernel on its local (batch-block,
    head-block), exactly the math the partitioner would otherwise have to
    reconstruct, and picks its schedule from its LOCAL head count. The GSPMD
    step builders provide the ambient mesh via ``jax.sharding.set_mesh``.

    Everywhere else this is ``flash_attention_qkv`` unchanged: with no
    ambient mesh (eager, plain-jit single device) or inside an already-manual
    region (the shard_map DP/PP/SP step bodies) there is nothing to wrap.
    """
    from jax.sharding import PartitionSpec as P

    from tpudist._jaxshim import ambient_auto_axes

    fn = functools.partial(flash_attention_qkv, causal=causal, **kw)
    batch, _, heads = qkv.shape[:3]
    mesh, auto = ambient_auto_axes(("data", "model"))
    if "data" in auto and batch % mesh.shape["data"]:
        # An undivisible batch cannot shard; drop the axis rather than die
        # (the partitioner then handles the batch dim — correct, slower).
        auto = auto - {"data"}
    if not auto:
        return fn(qkv)
    if "model" in auto and heads % mesh.shape["model"]:
        raise ValueError(
            f"flash attention under TP needs the model-axis size "
            f"{mesh.shape['model']} to divide num_heads={heads}")
    spec = P("data" if "data" in auto else None, None,
             "model" if "model" in auto else None)
    return jax.shard_map(fn, mesh=mesh, axis_names=frozenset(auto),
                         in_specs=(spec,), out_specs=spec,
                         check_vma=False)(qkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_vjp(q, k, v, causal, window, block_q, block_k, block_q_bwd,
               block_k_bwd, interpret):
    o, _ = _flash_forward(q, k, v, causal, window, block_q, block_k,
                          interpret)
    return o


def _flash_vjp_fwd(q, k, v, causal, window, block_q, block_k, block_q_bwd,
                   block_k_bwd, interpret):
    o, lse = _flash_forward(q, k, v, causal, window, block_q, block_k,
                            interpret)
    # named, so that a caller that rematerialises its layer can keep the
    # kernel's two results (``jax.checkpoint_policies.save_only_these_names(
    # *SAVED_BY_NAME)``) and not run the forward kernel a second time
    o, lse = checkpoint_name(o, SAVED_BY_NAME[0]), checkpoint_name(
        lse, SAVED_BY_NAME[1])
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, block_q, block_k, block_q_bwd,
                   block_k_bwd, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_backward(q, k, v, o, lse, g, causal, window, block_q_bwd,
                           block_k_bwd, interpret)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _scaled_q(q, d: int):
    """Softmax temperature folded into Q once (fp32 multiply, cast back to
    the MXU input dtype) — S = (scale·Q)Kᵀ needs no per-tile VPU rescale,
    and dK = dSᵀ·(scale·Q) comes out scaled for free in the backward."""
    scale = 1.0 / (d ** 0.5)
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _stream_geometry(t, tk, causal, window, block_q, block_k):
    block_q = min(block_q, _ceil_to(t, 8))
    block_k = min(block_k, _ceil_to(tk, 8))
    tq_pad = _ceil_to(t, block_q)
    tk_pad = _ceil_to(tk, block_k)
    band = _Band(causal=causal, window=window, block_q=block_q,
                 block_k=block_k, q_len=t, k_len=tk, nq=tq_pad // block_q,
                 nk=tk_pad // block_k)
    return band, tq_pad, tk_pad


def _stream_cost(band: _Band, products: int, b, h, d, isz, arrays: int,
                 rows: int):
    """What a streaming call claims: ``products`` matrix products and one
    exponential a score over the block pairs that run (not the square: a
    banded call would otherwise raise the step's FLOP count, and with it
    the model FLOP utilisation read from it, by work nobody does);
    ``arrays`` [B, T, H, D] operands and ``rows`` float32 row statistics
    moved once."""
    scores = b * h * band.pairs() * band.bq * band.bk
    t = band.nq * band.bq
    return pl.CostEstimate(
        flops=2 * products * scores * d, transcendentals=scores,
        bytes_accessed=arrays * b * t * h * d * isz + 4 * rows * b * h * t)


def _flash_forward(q, k, v, causal, window, block_q, block_k, interpret):
    b, t, h, d = q.shape
    tk, group = k.shape[1], h // k.shape[2]
    band, tq_pad, tk_pad = _stream_geometry(t, tk, causal, window, block_q,
                                            block_k)
    block_q, block_k = band.bq, band.bk

    # (B, T, H, D) → (B, H, T, D); pad T so the grid tiles exactly. Padded
    # keys are masked inside the kernel (k_len); padded q rows drop on exit.
    qt = jnp.moveaxis(_scaled_q(q, d), 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    if tq_pad != t:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, tq_pad - t), (0, 0)))
    if tk_pad != tk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, tk_pad - tk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, tk_pad - tk), (0, 0)))

    kernel = functools.partial(_flash_kernel, band=band, q_len=t, k_len=tk,
                               mask_k=tk_pad != tk)
    # a query head reads the key-value head of its group
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b_, h_, iq, s: (b_, h_ // group, band.k_block(iq, s)[2], 0))

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, band.nq, band.steps_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, s: (b_, h_, iq, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, s: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, iq, s: (b_, h_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, tq_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        # two products (S, P V); q, k, v in (k and v at their own head
        # count: less than the two arrays claimed), o and the logsumexp out
        cost_estimate=_stream_cost(band, 2, b, h, d, q.dtype.itemsize,
                                   arrays=4, rows=1),
        interpret=interpret,
    )(qt, kt, vt)

    out = out[:, :, :t, :]
    return jnp.moveaxis(out, 1, 2), lse


def _masked_scores(s, iq, ik, *, causal, block_q, block_k, q_len, k_len,
                   mask_k, keys_axis: int = 1, window: int | None = None):
    """Static mask specialization shared by the forward and both backward
    passes: build the (bq, bk) validity mask only under configs that need
    one — key padding (``mask_k``), causality (global-position tril with
    the k_len−q_len offset, matching the XLA ``attention``) or a window
    (of the keys causality allows, the nearest ``window``). Zero-padded q
    rows need NO mask anywhere: the forward drops them on the way out (its
    l==0 guard), and in the backward their dO and delta rows are zero, so
    every contribution they could make (dV += Pᵀ·dO, dS = P·(dP − δ))
    cancels exactly; the only hazard — exp(s − (−inf)) from their forward
    lse — is removed by the backward's lse clamp. Returns (masked scores,
    valid-or-None): the forward also zeroes its probabilities by
    ``valid``. ``keys_axis`` says which axis of ``s`` the keys lie on: 1
    for the streaming kernels' (bq, bk) tiles, 0 for the whole-sequence
    kernels' transposed (T_k, T_q) scores."""
    offset = k_len - q_len
    valid = None
    if mask_k:
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, keys_axis)
        valid = cols < k_len
    if causal:
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, keys_axis)
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1 - keys_axis)
        c = rows + offset >= cols
        if window is not None:
            c = jnp.logical_and(c, rows + offset - cols < window)
        valid = c if valid is None else jnp.logical_and(valid, c)
    if valid is not None:
        s = jnp.where(valid, s, NEG_INF)
    return s, valid


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, band: _Band, q_len: int,
                   k_len: int, mask_k: bool):
    """dQ pass: parallel over q blocks, k blocks stream sequentially.

    The (block_q, d) dQ tile accumulates in fp32 scratch across the k
    stream; the temperature (folded out of dS) is applied once per tile in
    the epilogue instead of once per (bq, bk) score tile."""
    iq = pl.program_id(2)
    step = pl.program_id(3)
    ik, run, _ = band.k_block(iq, step)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                                     # (bq, d), scaled
        k = k_ref[0, 0]                                     # (bk, d)
        v = v_ref[0, 0]                                     # (bk, d)
        do = do_ref[0, 0]                                   # (bq, d)
        lse = lse_ref[0, 0]                                 # (bq, 1)
        delta = delta_ref[0, 0]                             # (bq, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        s, _ = _masked_scores(s, iq, ik, causal=band.causal,
                              block_q=band.bq, block_k=band.bk, q_len=q_len,
                              k_len=k_len, mask_k=mask_k, window=band.window)
        # p from the saved statistics — no second softmax pass.
        p = jnp.exp(s - lse)                                 # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        ds = p * (dp - delta)                                # (bq, bk)
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, d)

    @pl.when(step == band.steps_k - 1)
    def _finish():
        dq_ref[0, 0, :, :] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, band: _Band,
                    group: int, q_len: int, k_len: int, mask_k: bool):
    """dKV pass: parallel over KV blocks; the query heads of the group, and
    under each the q blocks, stream sequentially.

    Each program owns one (block_k, d) dK tile and one dV tile in fp32
    scratch and streams Q/dO past them, of every query head that reads this
    key-value head: the group's sum is taken where the tiles lie.
    Everything stays (bq, bk)-oriented —
    probabilities are transposed only implicitly, by contracting over the q
    dim in the two gradient matmuls. (A materialized (1, bq) lse/delta row
    would need a sublane→lane relayout that Mosaic can't lower; a (bq, 1)
    column is native.) dK needs no epilogue scale: Q arrives pre-scaled, and
    dK = dSᵀ·(scale·Q) IS the scaled gradient."""
    ik = pl.program_id(2)
    member = pl.program_id(3)
    step = pl.program_id(4)
    iq, run, _ = band.q_block(ik, step)

    @pl.when(jnp.logical_and(member == 0, step == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(run)
    def _step():
        k = k_ref[0, 0]                                     # (bk, d)
        v = v_ref[0, 0]                                     # (bk, d)
        q = q_ref[0, 0]                                     # (bq, d), scaled
        do = do_ref[0, 0]                                   # (bq, d)
        lse = lse_ref[0, 0]                                 # (bq, 1)
        delta = delta_ref[0, 0]                             # (bq, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        s, _ = _masked_scores(s, iq, ik, causal=band.causal,
                              block_q=band.bq, block_k=band.bk, q_len=q_len,
                              k_len=k_len, mask_k=mask_k, window=band.window)
        p = jnp.exp(s - lse)                                 # (bq, bk)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        ds = p * (dp - delta)                                # (bq, bk)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bk, d)

    @pl.when(jnp.logical_and(member == group - 1,
                             step == band.steps_q - 1))
    def _finish():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal, window, block_q, block_k,
                    interpret):
    """Two-pass flash backward (see module docstring): a dQ pass parallel
    over q blocks and a dKV pass parallel over KV blocks, sharing the saved
    ``lse`` and the XLA-precomputed ``delta = rowsum(dO ∘ O)``."""
    b, t, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    scale = 1.0 / (d ** 0.5)
    band, tq_pad, tk_pad = _stream_geometry(t, tk, causal, window, block_q,
                                            block_k)
    block_q, block_k = band.bq, band.bk
    mask_k = tk_pad != tk
    isz = q.dtype.itemsize

    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                 # (b, t, h)
    delta = jnp.moveaxis(delta, -1, 1)                       # (b, h, t)

    qt = jnp.moveaxis(_scaled_q(q, d), 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    dot = jnp.moveaxis(g, 1, 2)
    # The forward's lse is padded to the FORWARD q-block multiple, which may
    # differ from this pass's (block_q_bwd): re-pad from the true length.
    # Fully-masked (padded) q rows carry lse = NEG_INF; exp(s - NEG_INF)
    # would overflow to inf → NaN via inf·0 in the matmuls, so clamp those
    # rows to 0 — with the clamp their contributions cancel exactly (zero
    # dO/delta rows), which is why the backward kernels need no q-row mask.
    # Both per-row stats ride in the (B, H, Tq, 1) layout (see _flash_kernel's
    # _finish for why rank-3 blocks don't lower on TPU).
    lse_safe = jnp.where(lse[:, :, :t] <= NEG_INF / 2, 0.0, lse[:, :, :t])
    if tq_pad != t:
        pad_q = ((0, 0), (0, 0), (0, tq_pad - t), (0, 0))
        qt = jnp.pad(qt, pad_q)
        dot = jnp.pad(dot, pad_q)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, tq_pad - t)))
        lse_safe = jnp.pad(lse_safe, ((0, 0), (0, 0), (0, tq_pad - t),
                                      (0, 0)))
    if tk_pad != tk:
        pad_k = ((0, 0), (0, 0), (0, tk_pad - tk), (0, 0))
        kt = jnp.pad(kt, pad_k)
        vt = jnp.pad(vt, pad_k)
    delta = delta[..., None]

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b_, h_, iq, s: (b_, h_, iq, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b_, h_, iq, s: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b_, h_, iq, s: (b_, h_ // group, band.k_block(iq, s)[2], 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, band=band, q_len=t,
                          k_len=tk, mask_k=mask_k),
        grid=(b, h, band.nq, band.steps_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, tq_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        # the model's work: dP and dQ (the recomputed scores are left out,
        # as the whole-sequence backward leaves them); q, k, v, dO in, dQ
        # out, the logsumexp and delta
        cost_estimate=_stream_cost(band, 2, b, h, d, isz, arrays=5, rows=2),
        interpret=interpret,
    )(qt, kt, vt, dot, lse_safe, delta)

    # dKV: one program a key-value head and k block; the group's members
    # and the q blocks in its band stream past it
    def q_index(b_, hk, ik, m, s):
        return b_, hk * group + m, band.q_block(ik, s)[2], 0

    k_spec = pl.BlockSpec((1, 1, block_k, d),
                          lambda b_, hk, ik, m, s: (b_, hk, ik, 0))
    q_spec_b = pl.BlockSpec((1, 1, block_q, d), q_index)
    row_spec_b = pl.BlockSpec((1, 1, block_q, 1), q_index)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, band=band, group=group, q_len=t,
                          k_len=tk, mask_k=mask_k),
        grid=(b, hkv, band.nk, group, band.steps_q),
        in_specs=[k_spec, k_spec, q_spec_b, q_spec_b, row_spec_b, row_spec_b],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((b, hkv, tk_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hkv, tk_pad, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        # the model's work: dV and dK
        cost_estimate=_stream_cost(band, 2, b, h, d, isz, arrays=6, rows=2),
        interpret=interpret,
    )(kt, vt, qt, dot, lse_safe, delta)

    dq = jnp.moveaxis(dq[:, :, :t, :], 1, 2)
    dk = jnp.moveaxis(dk[:, :, :tk, :], 1, 2)
    dv = jnp.moveaxis(dv[:, :, :tk, :], 1, 2)
    return dq, dk, dv


# -- whole-sequence schedule --------------------------------------------------
#
# For a sequence whose scores fit one VMEM block (ViT's 197 tokens) streaming
# is all overhead: a running max and normaliser nobody needs, pads and
# transposes in HBM on every call, two backward passes that each recompute
# the scores. Here one program holds the whole sequence of a group of heads:
#
# - the fused projection is read where ``in_proj`` wrote it: [B, T, H, 3, D]
#   is [B, T, H*3*D] in memory, and a group of ``g`` heads is ``g*3*D``
#   adjacent columns (whole 128-lane tiles, as many heads as VMEM takes:
#   ``_head_group``). T is the
#   block's full dimension, so nothing is padded or moved in HBM; the
#   output lands as [B, T, H*D], the layout ``out_proj`` reads, and the
#   backward writes dq | dk | dv as one block of the projection's cotangent;
# - scores are kept TRANSPOSED, (T_k, T_q): the softmax's max and sum then
#   run down the sublanes (elementwise on the VPU, no cross-lane shuffle),
#   and every per-query statistic (logsumexp, delta) is a lane-dense
#   (1, T_q) row, which is also how the logsumexp is stored: [B, H/g, g, T]
#   float32 (a (T, 1) column would be tiled to 128 lanes in HBM);
# - forward: S^T = K Q^T, a plain float32 softmax over the one tile, the
#   normalised probabilities cast to the input dtype (as the XLA path has
#   them), O = P V. Backward: S^T and P^T again from the saved logsumexp,
#   then dV = P^T dO, dP^T = V dO^T, delta = colsum(P^T o dP^T) (XLA's own
#   softmax transpose; equals rowsum(dO o O) without reading O), dS, dK =
#   dS^T Q, dQ = dS K: five products, one transposed operand (dQ's);
# - the temperature multiplies the float32 scores and gradients in the
#   kernel, so q is never rewritten; causal masking shares
#   ``_masked_scores`` with the streaming kernels.

def _whole_seq_vmem_bytes(t: int, head_dim: int, group: int,
                          itemsize: int) -> int:
    """VMEM one backward program (the larger of the two) holds: its qkv,
    dO and dqkv blocks, double-buffered by the pipeline, and six float32
    (T, T) tiles (S^T, P^T, dP^T, dS^T and the casts the products read)."""
    rows = _ceil_to(t, 16)
    io = 2 * rows * group * head_dim * (3 + 3 + 1) * itemsize
    return io + 6 * _ceil_to(t, 8) * _ceil_to(t, _LANES) * 4


def _head_group(t: int, heads: int, head_dim: int, itemsize: int):
    """Heads per program: the largest divisor of ``heads`` whose q | k | v
    columns (``g * 3 * head_dim``, and with them the output's ``g *
    head_dim``) fill whole 128-lane tiles and whose program fits the VMEM
    budget. The largest, because a block's rows are ``g * 3 * head_dim``
    contiguous elements of HBM: at two heads of ViT-B/16's twelve a program
    that only copies moves 207 GB/s, and forward + backward takes 3.08 ms
    against 2.15 ms with all twelve (docs/ATTENTION.md). None where no
    divisor does both (an odd head count at head_dim 64, a long sequence):
    such a call streams."""
    for g in range(heads, 0, -1):
        if (heads % g == 0 and (g * 3 * head_dim) % _LANES == 0
                and _whole_seq_vmem_bytes(t, head_dim, g,
                                          itemsize) <= _VMEM_BUDGET):
            return g
    return None


def schedule_for(seq: int, heads: int, head_dim: int, dtype) -> str:
    """The schedule ``flash_attention_qkv`` runs at a static shape:
    ``WHOLE_SEQ`` for a self-attention whose heads group onto lane tiles
    with their scores inside the VMEM budget, ``STREAMING`` otherwise."""
    if _head_group(seq, heads, head_dim,
                   jnp.dtype(dtype).itemsize) is not None:
        return WHOLE_SEQ
    return STREAMING


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention_qkv(qkv: jax.Array, causal: bool = False,
                        interpret: bool | None = None):
    """Fused self-attention on the projection's own layout: ``qkv`` is
    [B, T, H, 3, D] (head-major q | k | v, as ``MultiHeadAttention``'s
    ``in_proj`` writes it), the result [B, T, H, D]. Where the shape takes
    the whole-sequence schedule (``schedule_for``) the kernels read and
    write that layout in place (the gradient arrives as [B, T, H, 3, D]
    too); any other shape goes through slices and the streaming
    ``flash_attention``. Numerics as ``flash_attention``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, t, h, _, d = qkv.shape
    if schedule_for(t, h, d, qkv.dtype) == WHOLE_SEQ:
        return _qkv_vjp(qkv, causal, interpret)
    return flash_attention(qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :],
                           causal=causal, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _qkv_vjp(qkv, causal, interpret):
    return _qkv_forward(qkv, causal, interpret)[0]


def _qkv_vjp_fwd(qkv, causal, interpret):
    o, lse = _qkv_forward(qkv, causal, interpret)
    return o, (qkv, lse)


def _qkv_vjp_bwd(causal, interpret, res, g):
    qkv, lse = res
    return (_qkv_backward(qkv, lse, g, causal, interpret),)


_qkv_vjp.defvjp(_qkv_vjp_fwd, _qkv_vjp_bwd)


def _head_columns(ref, j: int, d: int):
    """Head ``j`` of a (1, T, g*3*d) block: its q, k, v as (T, d)."""
    c = j * 3 * d
    return (ref[0, :, c:c + d], ref[0, :, c + d:c + 2 * d],
            ref[0, :, c + 2 * d:c + 3 * d])


def _scores_t(q, k, *, scale: float, causal: bool):
    """S^T = K Q^T at the softmax temperature, (T_k, T_q) float32."""
    st = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    t = q.shape[0]
    st, _ = _masked_scores(st, 0, 0, causal=causal, block_q=t, block_k=t,
                           q_len=t, k_len=t, mask_k=False, keys_axis=0)
    return st


def _whole_seq_fwd_kernel(qkv_ref, o_ref, lse_ref, *, group: int, d: int,
                          scale: float, causal: bool):
    for j in range(group):
        q, k, v = _head_columns(qkv_ref, j, d)
        st = _scores_t(q, k, scale=scale, causal=causal)
        m = jnp.max(st, axis=0, keepdims=True)               # (1, Tq)
        p = jnp.exp(st - m)
        l = jnp.sum(p, axis=0, keepdims=True)
        p = p * (1.0 / l)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (Tq, d)
        o_ref[0, :, j * d:(j + 1) * d] = o.astype(o_ref.dtype)
        lse_ref[0, 0, j:j + 1, :] = m + jnp.log(l)


def _whole_seq_bwd_kernel(qkv_ref, do_ref, lse_ref, dqkv_ref, *, group: int,
                          d: int, scale: float, causal: bool):
    for j in range(group):
        q, k, v = _head_columns(qkv_ref, j, d)
        do = do_ref[0, :, j * d:(j + 1) * d]                 # (Tq, d)
        st = _scores_t(q, k, scale=scale, causal=causal)
        p = jnp.exp(st - lse_ref[0, 0, j:j + 1, :])          # (Tk, Tq)
        dv = jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (Tk, d)
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (Tk, Tq)
        delta = jnp.sum(p * dp, axis=0, keepdims=True)       # (1, Tq)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk = jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (Tk, d)
        dq = jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (Tq, d)
        c = j * 3 * d
        for i, grad in enumerate((dq, dk, dv)):
            dqkv_ref[0, :, c + i * d:c + (i + 1) * d] = grad.astype(
                dqkv_ref.dtype)


def _whole_seq_specs(b: int, t: int, h: int, d: int, itemsize: int):
    g = _head_group(t, h, d, itemsize)
    qkv_spec = pl.BlockSpec((1, t, g * 3 * d), lambda b_, g_: (b_, 0, g_))
    o_spec = pl.BlockSpec((1, t, g * d), lambda b_, g_: (b_, 0, g_))
    lse_spec = pl.BlockSpec((1, 1, g, t), lambda b_, g_: (b_, g_, 0, 0))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))
    return g, qkv_spec, o_spec, lse_spec, params


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _qkv_forward(qkv, causal, interpret):
    b, t, h, _, d = qkv.shape
    isz = qkv.dtype.itemsize
    g, qkv_spec, o_spec, lse_spec, params = _whole_seq_specs(b, t, h, d, isz)
    out, lse = pl.pallas_call(
        functools.partial(_whole_seq_fwd_kernel, group=g, d=d,
                          scale=1.0 / (d ** 0.5), causal=causal),
        grid=(b, h // g),
        in_specs=[qkv_spec],
        out_specs=[o_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * d), qkv.dtype),
                   jax.ShapeDtypeStruct((b, h // g, g, t), jnp.float32)],
        compiler_params=params,
        # The algorithm's cost at the true length: two products, one
        # exponential a score; qkv in, o and the logsumexp out.
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * t * t * d,
            transcendentals=b * h * t * t,
            bytes_accessed=4 * b * t * h * d * isz + 4 * b * h * t),
        interpret=interpret,
    )(qkv.reshape(b, t, h * 3 * d))
    return out.reshape(b, t, h, d), lse


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _qkv_backward(qkv, lse, g_out, causal, interpret):
    b, t, h, _, d = qkv.shape
    isz = qkv.dtype.itemsize
    g, qkv_spec, o_spec, lse_spec, params = _whole_seq_specs(b, t, h, d, isz)
    dqkv = pl.pallas_call(
        functools.partial(_whole_seq_bwd_kernel, group=g, d=d,
                          scale=1.0 / (d ** 0.5), causal=causal),
        grid=(b, h // g),
        in_specs=[qkv_spec, o_spec, lse_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h * 3 * d), qkv.dtype),
        compiler_params=params,
        # Four gradient products (the recomputed scores are not the
        # model's work and are left out); qkv, dO and the logsumexp in,
        # dqkv out.
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * t * t * d,
            transcendentals=b * h * t * t,
            bytes_accessed=7 * b * t * h * d * isz + 4 * b * h * t),
        interpret=interpret,
    )(qkv.reshape(b, t, h * 3 * d),
      g_out.reshape(b, t, h * d), lse)
    return dqkv.reshape(b, t, h, 3, d)
