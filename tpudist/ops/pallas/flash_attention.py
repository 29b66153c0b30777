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

- grid = (batch, kv_heads, q_blocks, k_steps), k innermost and marked
  "arbitrary" (sequential) so the running-softmax state carried in VMEM
  scratch is valid across k steps; batch/head/q are "parallel".
- a program belongs to one KEY-VALUE head and holds all ``G = H / Hkv``
  query heads that read it (grouped-query attention; ``G = 1`` where every
  query head has its own): the members are a static loop over one resident
  K / V block, so K and V are fetched once a group, a layer makes ``G``
  times fewer grid steps, and the rows that fill the MXU come from heads,
  not from positions. A q block can then be short along the sequence and
  a step wide in keys, and under a window the steps slide with the band
  (``_Band``: block offsets are elements, ``pl.Element``, not block
  multiples), so a q block walks the keys its rows may see and little
  more (``_default_blocks`` chooses the blocks from the static shape).
- operands lie as [B, Hkv, G, T, D]: a block is G tiles of (rows, D), each
  whole rows of one head. Who writes them there: for a decoder layer that
  norms or rotates q and k, the Pallas pass that does so
  (``ops/pallas/qk_norm_rope.py``, through ``flash_attention_laid``: q and k
  arrive laid, dQ and dK leave laid); for v, o and dO, and for q and k of
  every other caller, XLA by ``_operand`` / ``_result``. (Read as [B, T,
  H*D], where the projections wrote them, the kernels ran as fast alone,
  but the decoder's step lost 44 ms to the layouts XLA then gave the norm
  and RoPE around the calls: measured in PR 33 and taken out.)
- Q stays resident in VMEM for all k steps of a q block (scaled by the
  softmax temperature once, into scratch, at the block's first step: S =
  (scale·Q)Kᵀ needs no per-tile VPU rescale); K/V blocks stream HBM→VMEM via
  the BlockSpec pipeline (Pallas double-buffers automatically).
- online softmax in fp32, a member each: the running max ``m`` and
  normalizer ``l`` live in (block_q, 128) VMEM scratch, member ``j`` in lane
  ``j``; the unnormalized accumulators in (G, block_q, head_dim) fp32.
- the two matmuls (S = QKᵀ, O += P·V) hit the MXU in the input dtype
  (bf16 under the AMP policy) with fp32 accumulation; masking/exp/rescale
  fuse into the VPU between them.
- masking is by GLOBAL position: causal (rows ≥ cols), a window (of those,
  the nearest ``window`` keys) and key-validity
  (cols < true key length, so sequence lengths that aren't block multiples —
  ViT's 197 tokens — are padded then exactly masked). The mask is built
  ONLY for block pairs that the band's edges cross (``_Band.interior``: the
  diagonal, the window's far edge, the last block of a ragged key length),
  once a step for all the members; a pair inside the band runs a mask-free
  VPU path, and a call that statically needs no mask (exact tiling,
  non-causal) holds no masked path at all. The k dimension of
  the grid counts steps inside a q block's band (``_Band``): with a window
  it is as long as the band is wide, not as the sequence; a step past the
  band's end (above the diagonal) is skipped with ``pl.when`` and fetches
  nothing, its offset staying where it was.
- the per-row logsumexp leaves as [B, Hkv, T, G] float32 (the members on the
  minor dimension: the block's full dimension).
- a third shape of what a resident block needs (rev 6): under the mask of
  training by diffusion over blocks (``block_diffusion = (L, block)``: a
  noised and a clean copy of a row in one self-attention) it is two pieces
  of the streamed side, not one band (``_DiffusionBand``); the same
  kernels walk the first piece's steps and then the second's. Of a tile
  that an edge of that mask leaves mostly empty only a part runs (rev 7,
  ``_DiffusionBand.squares``): in the forward every tile whose allowed
  scores lie in one half of its keys runs that half (the noisy diagonal's
  tile of 512 x 1,024, the last tile of a clean prefix whose far half no
  row may see); in the dQ and dKV passes the noisy diagonal, blocks of 4
  positions, is walked as four squares of 128 x 128. ``_step`` takes the
  rows and keys of the tile it spans. A causal or windowed band states no
  squares, and traces as before.

Streaming backward (the two-pass schedule):

FlashAttention-2's core lesson is that the backward is where naive tiling
drowns: it must be two dedicated passes with the right grid parallelism,
each recomputing probabilities from the forward's saved per-row logsumexp —
never one recompute-everything loop and never an O(T²) tensor.

- **dQ pass**: grid (batch, kv_heads, q_blocks, k_steps), k innermost
  sequential — each program owns the group's (G, block_q, d) dQ tiles and
  streams K/V blocks; the temperature is applied once per tile in the
  epilogue. It also takes ``delta = rowsum(dO ∘ O)`` of its rows, once a q
  block, from the dO and O blocks it holds, and writes it out beside dQ:
  XLA makes no float32 copy of dO or O for it.
- **dKV pass**: grid (batch, kv_heads, k_blocks, q_steps), the last
  sequential — each program owns one (block_k, d) dK/dV tile in fp32 VMEM
  scratch and streams past it the Q/dO rows of the whole group (the
  group's sum is taken in VMEM; under a window the q steps slide with the
  band as the forward's k steps do). The temperature rides on the resident
  K (scaled once a program into scratch) and on dK's epilogue, so the q
  blocks that stream past are used as they arrive.
- both reuse the forward's saved logsumexp and the dQ pass's delta instead
  of rematerializing the softmax normalization per tile, so each pass is
  exactly two MXU matmuls of recompute (S and dP) plus its two gradient
  matmuls. q, o, dO, dQ, dK, dV lie as the forward's operands do.
- accumulators are fp32 over bf16 MXU operands; each of the three kernels
  has blocks of its own (``_Blocks``; ``block_q_bwd``/``block_k_bwd`` set
  both backward passes' for tests), since the dKV pass wants its resident
  tile on the KV dim while the forward and dQ want it on Q.
- zero-padded Q rows cancel exactly (their dO and delta rows are zero), so
  only key-padding, causality and a window ever generate a mask — the same
  static specialization as the forward.

Whether this kernel actually beats XLA attention *in training* on a real
chip is decided by measurement, not by this docstring: the dispatch layer
(``tpudist/ops/attention_dispatch``) A/Bs both backends per shape and
caches the winner per device kind. ``KERNEL_REV`` below invalidates those
cached verdicts whenever the kernel changes.

Falls back to interpreter mode off-TPU so CPU tests exercise the same kernel.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
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
#   rev 5: a streaming program holds a key-value head's whole group of
#          query heads; blocks from (T, window, group); no mask inside the
#          band; steps that slide with a window's band (element offsets);
#          delta taken in the dQ pass; K scaled in the dKV pass.
#   rev 6: the streaming schedule takes the mask of training by diffusion
#          over blocks: two pieces of the streamed side a resident block.
#   rev 7: under that mask a tile that an edge leaves mostly empty runs the
#          part the mask reaches: the noised diagonal as squares of 128,
#          the near half of a clean prefix's last tile.
#   rev 8: an entry for queries and keys wider than the values and a rotated
#          key that all heads share (latent attention's training form:
#          ``mla_attention.py``, which uses this file's band and blocks);
#          the entries above run as they did.
KERNEL_REV = 8

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
    """Which score tiles of a streaming kernel hold a score the mask allows,
    as static numbers and as functions of a block index.

    Query row ``i`` sees key ``j`` where ``j <= i + offset`` (causal;
    ``offset = k_len - q_len``, the XLA ``attention``'s convention) and, with
    a window, ``i + offset - j < window``. A kernel keeps one side RESIDENT,
    in aligned blocks (the forward and dQ passes a q block of ``bq`` rows,
    the dKV pass a k block of ``bk`` keys), and STREAMS the other past it
    (``stream``: "k" or "q") in steps of that side's block. The innermost
    grid dimension counts those steps, ``steps`` of them: the most any
    resident block needs.

    Where the steps start (``span``): without a window at position 0, every
    step an aligned block, counted from the first one the resident block
    needs. With a window the steps SLIDE with the band: they start at the
    first position the resident block needs, rounded down to the
    ``granule`` (block offsets are elements, ``pl.Element``), so a q block
    of 256 rows under a window of 1,024 walks 1,280 keys, not the 2,048 of
    the two aligned blocks of 1,024 its band straddles. A step past the
    high end runs nothing, and its offset stays at the last step's, so the
    pipeline fetches nothing for it either."""

    copies = 1          # one row of positions (``_DiffusionBand`` lays two)
    squares = None      # a tile that runs, runs whole (``_DiffusionBand``)

    def __init__(self, *, causal, window, block_q, block_k, q_len, k_len,
                 stream="k"):
        self.causal, self.window, self.stream = causal, window, stream
        self.q_len, self.k_len = q_len, k_len
        self.offset = k_len - q_len
        self.bq = min(block_q, _ceil_to(q_len, 8))
        self.bk = min(block_k, _ceil_to(k_len, 8))
        lens, blocks = (q_len, k_len), (self.bq, self.bk)
        side = 0 if stream == "k" else 1           # the resident side
        self.n = -(-lens[side] // blocks[side])    # resident blocks
        self._res, self.b = blocks[side], blocks[1 - side]
        self.granule = math.gcd(self.b, _LANES)
        self._last = None                          # a sliding start's limit
        self.steps = max(1, max(hi - lo + 1 for _, lo, hi in
                                map(self.span, range(self.n))))
        pads = [self.n * self._res, _ceil_to(lens[1 - side], self.b)]
        if window is not None:
            pads[1] = max(_ceil_to(lens[1 - side], self.granule),
                          self.steps * self.b)
            self._last = pads[1] - self.steps * self.b
        self.tq_pad, self.tk_pad = pads if stream == "k" else pads[::-1]

    def _needs(self, i):
        """(first, last) position of the streamed side that the resident
        block ``i`` holds an allowed score with; last < first where none."""
        first = i * self._res
        if self.stream == "k":
            lo = (0 if self.window is None
                  else _max(first + self.offset - self.window + 1, 0))
            hi = (self.k_len - 1 if not self.causal
                  else _min(first + self.bq - 1 + self.offset,
                            self.k_len - 1))
        else:
            lo = _max(first - self.offset, 0) if self.causal else 0
            hi = (self.q_len - 1 if self.window is None
                  else _min(first + self.bk + self.window - 2 - self.offset,
                            self.q_len - 1))
        return lo, hi

    # floor division rounds down for negative numbers too (Python and jnp
    # alike), so a block with nothing to see gets a last step below its first

    def span(self, i):
        """(position of step 0, first step that runs, last step that runs)
        of resident block ``i``, steps counted in blocks from the start."""
        lo, hi = self._needs(i)
        start = 0
        if self.window is not None:
            start = lo // self.granule * self.granule
            if self._last is not None:
                start = _min(start, self._last)
        return start, (lo - start) // self.b, (hi - start) // self.b

    def step(self, i, s):
        """(position of grid step ``s`` of resident block ``i``, whether it
        runs, the position to fetch)."""
        start, lo, hi = self.span(i)
        pad = self.tk_pad if self.stream == "k" else self.tq_pad
        # in granules, multiplied out last: the chip's compiler has to see
        # that the offset is a multiple of the memory tiling
        g = self.granule
        fetch = jnp.clip(start // g + jnp.minimum(lo + s, hi) * (self.b // g),
                         0, (pad - self.b) // g) * g
        return start + (lo + s) * self.b, lo + s <= hi, fetch

    @property
    def masks(self) -> bool:
        """Whether any tile of the call needs a mask (static): a non-causal
        call whose key length tiles exactly holds none."""
        return self.causal or self.tk_pad != self.k_len

    def interior(self, row0, col0):
        """Whether every score of the tile at (row0, col0) is one the mask
        allows, so that the tile builds no mask: no padded key, its last key
        at or below the diagonal for its first row, its first key inside the
        window of its last row."""
        inside = col0 + self.bk <= self.k_len
        if self.causal:
            first = row0 + self.offset
            inside &= first >= col0 + self.bk - 1
            if self.window is not None:
                inside &= first + self.bq - 1 - col0 < self.window
        return inside

    def valid(self, row0, col0):
        """The mask of the tile at (row0, col0): built once a step, shared
        by the group's members. Zero-padded q rows need NO mask anywhere:
        the forward drops them on the way out (its l==0 guard), and in the
        backward their dO and delta rows are zero, so every contribution
        they could make (dV += Pᵀ·dO, dS = P·(dP − δ)) cancels exactly; the
        only hazard — exp(s − (−inf)) from their forward lse — is removed
        by the backward's lse clamp."""
        return _valid((self.bq, self.bk), row0, col0, causal=self.causal,
                      q_len=self.q_len, k_len=self.k_len,
                      mask_k=self.tk_pad != self.k_len, window=self.window)

    def tiles(self) -> list[tuple[int, int]]:
        """(first row, first key) of every tile that runs (static)."""
        out = []
        for i in range(self.n):
            start, lo, hi = self.span(i)
            for s in range(lo, hi + 1):
                at = (i * self._res, start + s * self.b)
                out.append(at if self.stream == "k" else at[::-1])
        return out

    def pairs(self) -> int:
        """Tiles that run (static): what a call's cost is counted from, so
        that a banded call does not claim the square's work."""
        return len(self.tiles())

    def scores(self) -> int:
        """Scores the programs run (static): every tile that runs, whole."""
        return self.pairs() * self.bq * self.bk

    def fill(self) -> float:
        """Scores the mask allows over scores the programs run (static):
        how closely the tiles follow the band."""
        if not self.causal:
            allowed = self.q_len * self.k_len
        else:
            last = np.arange(self.q_len) + self.offset
            first = (0 if self.window is None
                     else np.maximum(last - self.window + 1, 0))
            allowed = int(np.maximum(
                np.minimum(last, self.k_len - 1) - first + 1, 0).sum())
        return allowed / self.scores()


def _pick(cond, a, b):
    """``a if cond else b`` for a static condition, ``jnp.where`` for one on
    the device (inside a kernel or an index map)."""
    if isinstance(cond, (bool, np.bool_)):
        return a if cond else b
    return jnp.where(cond, a, b)


_FAR = 1 << 30      # further than any block number: a bound that never binds


class _DiffusionBand:
    """``_Band``'s statements for the mask of training by diffusion over
    blocks (``parallel/ring_attention.py::block_diffusion_mask``): a
    self-attention over ``2 L`` positions, a noised copy of a row then the
    clean row, in blocks of ``block`` positions. With ``n()`` a position's
    block within its copy, a noisy query sees the noisy keys of its own
    block and the clean keys of the blocks before it, a clean query the
    clean keys up to its own block's end, and no query a noisy key of
    another block: ``L (L + block)`` scores of ``4 L^2``.

    Each copy is padded apart to ``half``, a multiple of both blocks
    (``copies = 2``: ``_fit``), so a tile lies in one copy on either side.
    What a resident block needs of the streamed side is then two PIECES, not
    one band: a noisy q block its own diagonal blocks of the noisy keys and
    a prefix of the clean ones, a clean k block (the dKV pass) the clean
    rows from its diagonal on and the noisy rows past it; a clean q block
    and a noisy k block need one. ``pieces`` states them in blocks of the
    streamed side, the grid's steps walk the first piece and then the
    second, and a step past both runs nothing and fetches nothing, as under
    ``_Band``. A tile inside a piece builds no mask; the mask is built on
    the tiles a block boundary of the mask crosses (every tile of the noisy
    diagonal, the last of a clean prefix) and on a ragged length's last.

    Of a tile that such an edge leaves mostly empty, only a PART runs (rev
    7): ``squares = (count, edge)`` squares of ``edge`` rows and keys down
    the tile's diagonal, from the key that ``part`` gives the tile. Which, a
    pass, follows from its blocks and from what was measured on the chip
    (docs/ATTENTION.md has the table). Where the rows are resident and the
    keys that stream past are wider (the forward: ``bk`` a multiple of
    ``bq``), ONE SQUARE of ``bq``: every tile whose allowed scores all lie
    in one ``bq``-wide run of its keys runs that run alone. Such are the
    noisy diagonal (the keys that face its rows) and the last tile of a
    clean prefix for a q block that starts on a key block's boundary (its
    far half is above the mask). Elsewhere (the dQ pass, whose tiles are
    square; the dKV pass, whose kernel has no room for both) the noisy
    diagonal is WALKED: its blocks of ``block`` positions lie in squares of
    ``grain`` rows and keys, the least multiple of the mask's block and a
    lane tile's 128 keys, and only those run. A mask whose blocks no such
    square of the q block holds (3 positions a block; 512 of them) states no
    walk, and its tiles run whole, as every tile of a ``_Band`` does."""

    causal, window, copies, masks = False, None, 2, True

    def __init__(self, *, length, block, block_q, block_k, stream="k"):
        self.length, self.block, self.stream = length, block, stream
        self.bq = min(block_q, _ceil_to(length, 8))
        self.bk = min(block_k, _ceil_to(length, 8))
        self.half = _ceil_to(length, math.lcm(self.bq, self.bk))
        self.q_len = self.k_len = 2 * length
        self.tq_pad = self.tk_pad = 2 * self.half
        self._res, self.b = ((self.bq, self.bk) if stream == "k"
                             else (self.bk, self.bq))
        self.n = 2 * (self.half // self._res)
        self.granule = math.gcd(self.b, _LANES)
        self.steps = max(1, max(a[1] + b[1] for a, b in
                                map(self.pieces, range(self.n))))
        grain = math.lcm(block, _LANES)
        self.squares = None
        if stream == "k" and self.bk > self.bq and self.bk % self.bq == 0:
            self.squares = 1, self.bq
        elif (self.bk % self.bq == 0 and self.bq % grain == 0
              and grain < self.bq):
            self.squares = self.bq // grain, grain

    def _n(self, x):
        """The block of position ``x`` within its copy."""
        if isinstance(x, (int, np.integer)):
            return x // self.block
        if self.block & (self.block - 1) == 0:       # vectors shift on the VPU
            return jax.lax.shift_right_logical(
                x, jnp.full_like(x, self.block.bit_length() - 1))
        return x // self.block

    def pieces(self, i):
        """((first, count), (first, count)): the two runs of streamed blocks
        (numbered over both copies) that resident block ``i`` holds an
        allowed score with; a count of 0 where a piece is empty."""
        per_half, ln, bl = self.half // self._res, self.length, self.block
        noisy = i < per_half
        lo = (i - _pick(noisy, 0, per_half)) * self._res
        hi = _min(lo + self._res, ln)                # past its last true row
        first, end = self._n(lo) * bl, _min((self._n(hi - 1) + 1) * bl, ln)
        if self.stream == "k":
            # noisy rows: their own blocks' noisy keys, then the clean keys
            # before the last row's block; clean rows: the clean keys up to
            # their last block's end
            a = _pick(noisy, 0, 1), _pick(noisy, first, 0), end
            b = 1, 0, _pick(noisy, self._n(hi - 1) * bl, 0)
        else:
            # noisy keys: the noisy rows of their own blocks; clean keys:
            # the clean rows from their first block on, then the noisy rows
            # past it
            a = _pick(noisy, 0, 1), first, _pick(noisy, end, ln)
            b = 0, first + bl, _pick(noisy, 0, ln)

        def run(copy, lo_, hi_):
            count = _pick((hi_ > lo_) & (lo < ln),
                          (hi_ - 1) // self.b - lo_ // self.b + 1, 0)
            return copy * (self.half // self.b) + lo_ // self.b, count
        return run(*a), run(*b)

    def step(self, i, s):
        """(position of grid step ``s`` of resident block ``i``, whether it
        runs, the position to fetch)."""
        (first_a, count_a), (first_b, count_b) = self.pieces(i)

        def at(step):
            return jnp.where(step < count_a, first_a + step,
                             first_b + step - count_a)
        g = self.granule
        fetch = jnp.clip(at(jnp.minimum(s, count_a + count_b - 1))
                         * (self.b // g), 0, (self.tk_pad - self.b) // g) * g
        return at(s) * self.b, s < count_a + count_b, fetch

    def _tile(self, row0, col0):
        """(first row and first key within their copies, and the bounds
        ``lo, hi`` of the tile's rule: a query of block ``n`` sees the keys
        of blocks ``n + lo .. n + hi``)."""
        clean_q, clean_k = row0 >= self.half, col0 >= self.half
        lo = _pick(clean_k, -_FAR, _pick(clean_q, _FAR, 0))
        hi = _pick(clean_k, _pick(clean_q, 0, -1), _pick(clean_q, -_FAR, 0))
        return (row0 - _pick(clean_q, self.half, 0),
                col0 - _pick(clean_k, self.half, 0), lo, hi)

    def interior(self, row0, col0):
        """Whether every score of the tile at (row0, col0) is one the mask
        allows: no padded key, and its first and last key's blocks inside
        what its last true row and its first row may see."""
        r, c, lo, hi = self._tile(row0, col0)
        last = _min(r + self.bq, self.length) - 1
        return ((c + self.bk <= self.length)
                & (self._n(c) >= self._n(last) + lo)
                & (self._n(c + self.bk - 1) <= self._n(r) + hi))

    def part(self, row0, col0):
        """(whether ``squares`` run in place of the tile at (row0, col0),
        the key within the tile that they start from). The keys any true row
        of the tile may see are ``first .. end - 1``: the one square is the
        ``bq``-wide run of keys that ``first`` lies in, and stands for the
        tile where ``end`` lies in it too; a walk stands for a tile of noisy
        rows by noisy keys, and starts where its rows do."""
        r, c, lo, hi = self._tile(row0, col0)
        last = _min(r + self.bq, self.length) - 1
        blocks = -(-self.length // self.block)

        def start_of(n):            # lo and hi may be _FAR: clip, then scale
            return _min(_max(n, 0), blocks) * self.block
        first = _max(c, start_of(self._n(r) + lo))
        end = _min(_min(c + self.bk, self.length),
                   start_of(self._n(last) + hi + 1))
        base = (first - c) // self.bq * self.bq
        if self.squares[0] == 1:
            return end <= c + base + self.bq, base
        return (lo == 0) & (hi == 0), base

    def valid(self, row0, col0, shape=None):
        """The mask of the tile at (row0, col0), or of a part of a tile: a
        rectangle of ``shape`` there (padded q rows need none:
        ``_Band.valid``)."""
        r, c, lo, hi = self._tile(row0, col0)
        shape = shape or (self.bq, self.bk)
        cols = c + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        n_q = self._n(r + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        n_k = self._n(cols)
        valid = jnp.logical_and(n_k >= n_q + lo, n_k <= n_q + hi)
        if self.half != self.length:
            valid = jnp.logical_and(valid, cols < self.length)
        return valid

    def tiles(self) -> list[tuple[int, int]]:
        """(first row, first key) of every tile that runs (static)."""
        out = []
        for i in range(self.n):
            for first, count in self.pieces(i):
                for s in range(first, first + count):
                    at = (i * self._res, s * self.b)
                    out.append(at if self.stream == "k" else at[::-1])
        return out

    def rects(self) -> list[tuple[int, int, int, int]]:
        """(first row, first key, rows, keys) of every rectangle of scores
        that runs (static): a tile, or the squares that stand for it."""
        out = []
        for row0, col0 in self.tiles():
            cut, base = self.part(row0, col0) if self.squares else (False, 0)
            if not cut:
                out.append((row0, col0, self.bq, self.bk))
                continue
            count, edge = self.squares
            out.extend((row0 + i * edge, col0 + base + i * edge, edge, edge)
                       for i in range(count))
        return out

    def pairs(self) -> int:
        """Rectangles that run (static)."""
        return len(self.rects())

    def scores(self) -> int:
        """Scores the programs run (static): the rectangles' areas, which is
        what a call claims (``_stream_cost``)."""
        return sum(rows * keys for _, _, rows, keys in self.rects())

    def fill(self) -> float:
        """Scores the mask allows (``L (L + block)`` where the blocks tile
        the row) over scores the programs run (static)."""
        ends = np.minimum((np.arange(self.length) // self.block + 1)
                          * self.block, self.length)
        return 2 * int(ends.sum()) / self.scores()


class _Mask(NamedTuple):
    """The mask a streaming call states (static): causality, a window of it,
    or diffusion over blocks ``(L, block)``."""
    causal: bool = False
    window: int | None = None
    block_diffusion: tuple | None = None

    def band(self, block_q, block_k, q_len, k_len, stream="k"):
        if self.block_diffusion is not None:
            length, block = self.block_diffusion
            return _DiffusionBand(length=length, block=block, block_q=block_q,
                                  block_k=block_k, stream=stream)
        return _Band(causal=self.causal, window=self.window, block_q=block_q,
                     block_k=block_k, q_len=q_len, k_len=k_len, stream=stream)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class _Blocks(NamedTuple):
    """(block_q, block_k) of the three streaming kernels."""
    fwd: tuple[int, int]
    dq: tuple[int, int]
    dkv: tuple[int, int]


def _default_blocks(t: int, tk: int, window: int | None,
                    group: int) -> _Blocks:
    """The blocks of a streaming call, from its static shape.

    One query head a program (``group == 1``): 128 (one MXU tile) up to
    1,024 positions, as every shape before the long ones ran; 1,024 beyond,
    where a step's fixed cost (about a third of a microsecond) would
    otherwise rival a small tile's products (measured with one head a
    program on a v5e at two sequences of 8,192, 32 heads over 4 of 128,
    forward + backward, ms: blocks of 256 / 512 / 1,024 took 116.1 / 55.3 /
    39.2 full and 36.2 / 22.5 / 20.7 under a window of 1,024).

    A group a program (measured at the same shape, eight heads a program,
    each kernel alone; docs/ATTENTION.md has the table): a tile has to be
    wide in keys, because the (bq, 1) column statistics and the
    accumulator's rescale cost a 128-key slice of the scores each whatever
    the tile's width, so blocks of 256 x 256 lose to 256 x 1,024 with a
    group too; what follows a window's band is a step that slides with it.
    Windowed: forward 256 x 1,280 (one step a q block) 4.02 ms against 5.59
    at 256 x 1,024 (two), dQ 128 x 1,152 4.70, dKV 1,280 x 256 (one step a
    k block) 4.09 against 4.67 at 512 x 512. Full: forward 512 x 1,024 9.75
    against 11.19 at 256 x 1,024, dQ 512 x 512 12.43, dKV 512 x 1,024
    13.43. Another window takes the same resident blocks and steps of at
    most 1,280 positions that span its band (``_band_steps``); nothing else
    has been measured."""
    if group == 1:
        one = (128 if t <= 1024 else 1024), (128 if tk <= 1024 else 1024)
        return _Blocks(one, one, one)
    if window is not None:
        return _Blocks((256, _band_steps(window, 256)),
                       (128, _band_steps(window, 128)),
                       (_band_steps(window, 256), 256))
    return _Blocks((512, 1024), (512, 512), (512, 1024))


# The most query heads a streaming program holds. A larger group is split
# over programs of this many: k and v are repeated ``group / 8`` times on the
# head axis, so each copy serves eight query heads and the kernels are the
# ones measured at eight (JAX's transpose of the repeat sums dK and dV over
# the copies). Sixteen members in one program need more than the 32 MiB of
# VMEM a program may hold (refused on the chip at 2 x 8,192, 32 heads over 2
# of 128), and the dKV kernel's unrolled members would be twice its code.
_MAX_GROUP = 8


def _programs_of(group: int) -> int:
    """Programs a key-value head's group of query heads is split over."""
    return group // _MAX_GROUP if group % _MAX_GROUP == 0 else 1


def _band_steps(window: int, resident: int, most: int = 1280) -> int:
    """The streamed side's block under a window: what a resident block's
    band spans (``window + resident - 1`` positions), in as few equal steps
    as stay within ``most`` positions each, whole 128s (one step of 1,280
    keys for 256 rows under a window of 1,024)."""
    span = _ceil_to(window + resident - 1, _LANES)
    steps = -(-span // most)
    return _ceil_to(-(-span // steps), _LANES)


def _member(ref, j: int):
    """Member ``j``'s (rows, d) tile of a group's (G, rows, d) block; the
    block itself where one head is a program's whole group."""
    return ref if len(ref.shape) == 2 else ref.at[j]


def _scaled(q, scale: float):
    """Q at the softmax temperature (fp32 multiply, cast back to the MXU
    input dtype): S = (scale·Q)Kᵀ needs no per-tile VPU rescale, and dK =
    dSᵀ·(scale·Q) comes out scaled for free in the backward."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _valid(shape, row0, col0, *, causal, q_len, k_len, mask_k,
           keys_axis: int = 1, window: int | None = None):
    """The validity mask of one score tile whose first row and first key are
    global positions ``row0`` and ``col0``, or None under a configuration
    that statically needs none: key padding (``mask_k``), causality (tril
    with the k_len−q_len offset, matching the XLA ``attention``) or a window
    (of the keys causality allows, the nearest ``window``). ``keys_axis``
    says which axis of the tile the keys lie on: 1 for the streaming
    kernels' (bq, bk) tiles, 0 for the whole-sequence kernels' transposed
    (T_k, T_q) scores."""
    offset = k_len - q_len
    valid = None
    if mask_k or causal:
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, keys_axis)
    if mask_k:
        valid = cols < k_len
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 - keys_axis)
        c = rows + offset >= cols
        if window is not None:
            c = jnp.logical_and(c, rows + offset - cols < window)
        valid = c if valid is None else jnp.logical_and(valid, c)
    return valid


def _on_band(band: _Band, row0, col0, run, step_fn):
    """Run ``step_fn(valid)`` for the tile at (row0, col0) where the step
    runs: with the tile's mask where an edge of the band crosses it, with
    None (no mask is built) inside the band or where the call needs none.
    Where the band states that squares on the tile's diagonal hold every
    score of it that the mask allows (``band.squares``; ``_Band`` states
    none), ``step_fn(valid, rows, keys, looped)`` runs for each square in
    the tile's place: ``rows`` and ``keys`` are the slices of the tile's
    rows and keys that it spans, ``valid`` its own mask. Several squares are
    a loop on the device, and so are the members of a square that is alone
    (``looped``): the kernels are nearly as long as the chip's instruction
    memory, and squares that came with copies of the tile's code made every
    tile slow (docs/ATTENTION.md has the measurement)."""
    if not band.masks:
        pl.when(run)(lambda: step_fn(None))
        return
    if band.squares:
        count, edge = band.squares
        cut, base = band.part(row0, col0)

        def square(i, carry=None):
            row = pl.multiple_of(i * edge, edge) if count > 1 else 0
            first = pl.multiple_of(base + row, edge)
            step_fn(band.valid(row0 + row, col0 + first, (edge, edge)),
                    pl.ds(row, edge), pl.ds(first, edge), looped=count == 1)

        @pl.when(jnp.logical_and(run, cut))
        def _squares():
            if count == 1:
                square(0)
            else:
                jax.lax.fori_loop(0, count, square, None)
        run = jnp.logical_and(run, jnp.logical_not(cut))
    inside = band.interior(row0, col0)
    pl.when(jnp.logical_and(run, inside))(lambda: step_fn(None))
    pl.when(jnp.logical_and(run, jnp.logical_not(inside)))(
        lambda: step_fn(band.valid(row0, col0)))


def _whole(at):
    """The index of a block's rows (or keys) that a step spans: all of them
    where the step is the tile (``at`` None), a square's slice otherwise."""
    return (Ellipsis, slice(None)) if at is None else (at, at)


def _members(group: int, member, looped: bool):
    """``member(j)`` for each member of the group: unrolled where the step
    is the tile or one square of several (a walked diagonal's: the squares
    are the loop), a loop on the device (``looped``) where it is one square
    for the tile, which unrolled would be half a tile's code again."""
    if not looped or group == 1:
        for j in range(group):
            member(j)
    else:
        jax.lax.fori_loop(0, group, lambda j, _: member(j), None)


def _column(ref, r, j):
    """Member ``j``'s (rows, 1) column of a row statistic whose members lie
    on the lanes: a slice where ``j`` is static, picked out of the rows by a
    lane mask where a loop on the device counts it."""
    if isinstance(j, int):
        return ref[r, j:j + 1]
    x = ref[r, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == j, x, 0.0), axis=1, keepdims=True)


def _set_column(ref, r, j, value):
    if isinstance(j, int):
        ref[r, j:j + 1] = value
        return
    x = ref[r, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    ref[r, :] = jnp.where(lane == j, value, x)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs_scr, m_scr, l_scr,
                  acc_scr, *, band: _Band, group: int, scale: float):
    row0 = pl.program_id(2) * band.bq
    step = pl.program_id(3)
    # the keys this step holds; tiles with no unmasked column (above the
    # diagonal, left of the window) are never reached or not run
    col0, run, _ = band.step(pl.program_id(2), step)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        qs_scr[...] = _scaled(q_ref[...], scale)

    def _step(valid, rows=None, keys=None, looped=False):
        # the tile, or the rows x keys of it that a part of the band spans
        (qr, r), (kr, _) = _whole(rows), _whole(keys)
        k = k_ref[kr]                                       # (bk, d)
        v = v_ref[kr]                                       # (bk, d)

        def member(j):
            q = _member(qs_scr, j)[qr]                   # (bq, d), scaled
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bq, bk) f32
            if valid is not None:
                s = jnp.where(valid, s, NEG_INF)
            m_prev = _column(m_scr, r, j)                   # (bq, 1)
            l_prev = _column(l_scr, r, j)
            m_next = jnp.maximum(m_prev,
                                 jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)                         # (bq, bk)
            if valid is not None:
                # a row with no key in this block yet: exp(-1e30 + 1e30)
                p = jnp.where(valid, p, 0.0)
            _set_column(m_scr, r, j, m_next)
            _set_column(l_scr, r, j, l_prev * alpha + jnp.sum(
                p, axis=1, keepdims=True))
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bq, d) f32
            acc = acc_scr.at[j]
            acc[qr] = acc[qr] * alpha + pv

        _members(group, member, looped)

    _on_band(band, row0, col0, run, _step)

    @pl.when(step == band.steps - 1)
    def _finish():
        m = m_scr[:, :group]                                # (bq, G)
        l = l_scr[:, :group]
        # Fully-masked rows (padded q rows, dropped on the way out): emit 0,
        # not NaN.
        l = jnp.where(l == 0.0, 1.0, l)
        for j in range(group):
            _member(o_ref, j)[...] = (
                acc_scr[j] / l[:, j:j + 1]).astype(o_ref.dtype)
        # Per-row logsumexp, saved for the backward recompute, the members
        # on the minor dimension: (B, Hkv, Tq, G). Mosaic requires the last
        # two block dims be (multiple-of-8, multiple-of-128-or-full-dim) — a
        # (1, block_q) row per member would put a size-1 slice in the
        # sublane position and fails to lower on real TPU hardware.
        lse_ref[...] = m + jnp.log(l)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_diffusion", "block_q", "block_k",
    "block_q_bwd", "block_k_bwd", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, window: int | None = None,
                    block_diffusion: tuple | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    block_q_bwd: int | None = None,
                    block_k_bwd: int | None = None,
                    interpret: bool | None = None):
    """Fused attention on split operands, the streaming schedule. ``q`` is
    [B, T, H, D], ``k`` and ``v`` [B, Tk, Hkv, D] (sequence-major, matching
    ``tpudist.parallel.ring_attention.attention``); returns [B, T, H, D].

    ``Hkv`` divides ``H``: query head ``j`` reads key-value head ``j // (H //
    Hkv)`` (grouped-query attention; one program holds a key-value head's
    whole group of query heads, and the backward sums dK / dV over it in
    VMEM; a group of sixteen or more is split over programs of eight:
    ``_MAX_GROUP``). ``window`` (static,
    with ``causal``) keeps, of the keys a query may see, the nearest
    ``window``; blocks wholly outside the band are neither run nor fetched
    (``_Band``). ``block_diffusion = (L, block)`` (static) states the mask
    of training by diffusion over blocks instead: a self-attention over ``2
    L`` positions, the noised copy first (``_DiffusionBand``; the XLA
    ``attention`` takes the same statement).

    Numerics: fp32 online softmax, MXU matmuls in the input dtype with fp32
    accumulation — same contract as the pure-XLA ``attention`` it replaces.

    Differentiable: the backward is flash too — two dedicated Pallas passes
    (a dKV pass parallel over KV blocks, a dQ pass parallel over Q blocks)
    recompute the probabilities blockwise from the saved per-row logsumexp
    and the precomputed ``delta = rowsum(dO ∘ O)``; no O(T²) tensor is ever
    materialized. ``block_q_bwd``/``block_k_bwd`` tune the backward blocks
    independently of the forward's (None = same as forward; the forward's
    default is ``_default_blocks`` of the lengths, the window and the
    group). The block arguments are for tests and measurements: they select
    no path.

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
    if block_diffusion is not None and (
            causal or not q.shape[1] == k.shape[1] == 2 * block_diffusion[0]):
        raise ValueError(
            f"block_diffusion={block_diffusion} states the whole mask of a "
            f"self-attention over twice its length: no causal, no window "
            f"(q {q.shape}, k {k.shape})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    split = _programs_of(q.shape[2] // k.shape[2])
    if split > 1:
        k, v = jnp.repeat(k, split, axis=2), jnp.repeat(v, split, axis=2)
    rule = _default_blocks(q.shape[1], k.shape[1], window,
                           q.shape[2] // k.shape[2])
    bwd_q, bwd_k = block_q_bwd or block_q, block_k_bwd or block_k
    blocks = _Blocks(
        (block_q or rule.fwd[0], block_k or rule.fwd[1]),
        (bwd_q or rule.dq[0], bwd_k or rule.dq[1]),
        (bwd_q or rule.dkv[0], bwd_k or rule.dkv[1]))
    return _flash_vjp(q, k, v, _Mask(causal, window, block_diffusion),
                      blocks, interpret)


def why_not_laid(t: int, group: int, window: int | None = None,
                 block_diffusion: tuple | None = None) -> str | None:
    """Why a caller cannot write q and k of a self-attention over ``t``
    positions into the kernels' layout itself (``flash_attention_laid``),
    None where it can (static, from the shape): one array then serves all
    three passes, so none of them may pad a row, and one program has to
    hold a key-value head's whole group."""
    if _programs_of(group) > 1:
        return (f"a group of {group} query heads is split over "
                f"{_programs_of(group)} attention programs")
    padded = (f"a row of {t} positions is padded in one of the three "
              f"attention passes")
    if block_diffusion is not None and 2 * block_diffusion[0] != t:
        return padded           # not the doubled row the mask states
    mask = _Mask(block_diffusion is None, window, block_diffusion)
    blocks = _default_blocks(t, t, window, group)
    bands = (mask.band(*blocks.fwd, t, t), mask.band(*blocks.dq, t, t),
             mask.band(*blocks.dkv, t, t, stream="q"))
    if any(not band.tq_pad == t == band.tk_pad for band in bands):
        return padded
    return None


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_diffusion", "interpret"))
def flash_attention_laid(q: jax.Array, k: jax.Array, v: jax.Array,
                         causal: bool = False, window: int | None = None,
                         block_diffusion: tuple | None = None,
                         interpret: bool | None = None):
    """``flash_attention`` for a caller that has written q and k where the
    kernels read them: ``q`` [B, Hkv, G, T, D] and ``k`` [B, Hkv, 1, T, D]
    (``ops/pallas/qk_norm_rope.py`` writes them so); ``v`` [B, T, Hkv, D] as
    ever; returns [B, T, H, D]. The cotangents of q and k leave laid too, as
    the dQ and dKV kernels write them. A self-attention at the shape's own
    blocks, of a length that no pass pads and a group one program holds
    (``why_not_laid``): anything else is refused."""
    b, hkv, group, t, d = q.shape
    mask = _Mask(causal, window, block_diffusion)
    why = why_not_laid(t, group, window, block_diffusion)
    if k.shape != (b, hkv, 1, t, d) or v.shape != (b, t, hkv, d) or (
            causal if block_diffusion is not None
            else window is not None and not causal):
        why = "not one self-attention's operands under a mask it takes"
    if why:
        raise ValueError(f"laid operands q {q.shape}, k {k.shape} with v "
                         f"{v.shape} under {mask}: {why}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_vjp(q, k, v, mask, _default_blocks(t, t, window, group),
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_vjp(q, k, v, mask, blocks, interpret):
    return _flash_forward(q, k, v, mask, blocks.fwd, interpret)[0]


def _flash_vjp_fwd(q, k, v, mask, blocks, interpret):
    o, lse = _flash_forward(q, k, v, mask, blocks.fwd, interpret)
    # named, so that a caller that rematerialises its layer can keep the
    # kernel's two results (``jax.checkpoint_policies.save_only_these_names(
    # *SAVED_BY_NAME)``) and not run the forward kernel a second time
    o, lse = checkpoint_name(o, SAVED_BY_NAME[0]), checkpoint_name(
        lse, SAVED_BY_NAME[1])
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(mask, blocks, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_backward(q, k, v, o, lse, g, mask, blocks.dq, blocks.dkv,
                           interpret)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _stream_cost(band: _Band, products: int, b, h, d, isz, arrays: int,
                 rows: int):
    """What a streaming call claims: ``products`` matrix products and one
    exponential a score over the tiles, or the squares that stand for one,
    that run (not the whole square of scores: a banded call would otherwise
    raise the step's FLOP count, and with it the model FLOP utilisation read
    from it, by work nobody does);
    ``arrays`` [B, T, H, D] operands and ``rows`` float32 row statistics
    moved once."""
    scores = b * h * band.scores()
    t = band.tq_pad
    return pl.CostEstimate(
        flops=2 * products * scores * d, transcendentals=scores,
        bytes_accessed=arrays * b * t * h * d * isz + 4 * rows * b * h * t)


# A [B, T, heads, D] operand as the streaming kernels read it, a key-value
# head's ``members`` heads (the group of query heads; 1 for k and v
# themselves) a block: moved to [B, Hkv, members, T, D], so that a block is
# whole (rows, D) tiles of one head each (XLA writes q and k there straight
# out of the norm and RoPE that make them, and those run with positions on
# the sublanes, where their tables lie; read as [B, T, H*D] where the
# projection wrote them, the decoder's step lost 44 ms to the layouts XLA
# then chose around the calls: PERF.md section 6, PR 33); rows padded to the
# block multiple (padded keys are masked inside the kernel, padded q rows
# drop on exit). Under the diffusion mask the positions are two copies of a
# row, each padded apart (``copies``). Since PR 41 q and k of a decoder layer
# that norms or rotates them do NOT come this way: the pass that norms and
# rotates writes them laid (``flash_attention_laid``): ``_operand`` hands an
# array of that rank on as it is, and ``_operand`` / ``_result`` move v, o,
# dO and dV, and everything of the callers that hand [B, T, H, D] over.

def _fit(x, axis: int, t: int, t_pad: int, copies: int = 1):
    """``x`` whose ``axis`` holds ``copies`` runs of positions one after
    another: each cut to its first ``t / copies`` and padded with zeros to
    ``t_pad / copies`` (nothing moves where the lengths are already so)."""
    have = x.shape[axis] // copies
    t, t_pad = t // copies, t_pad // copies
    if have == t == t_pad:
        return x
    lead = x.shape[:axis]
    x = x.reshape(lead + (copies, have) + x.shape[axis + 1:])
    x = jax.lax.slice_in_dim(x, 0, t, axis=axis + 1)
    if t_pad != t:
        x = jnp.pad(x, [(0, 0)] * (axis + 1) + [(0, t_pad - t)]
                    + [(0, 0)] * (x.ndim - axis - 2))
    return x.reshape(lead + (copies * t_pad,) + x.shape[axis + 2:])


def _operand(x, hkv: int, t_pad: int, copies: int = 1):
    if x.ndim == 5:             # laid by its caller, which pads no row
        return x
    b, t, h, d = x.shape
    x = jnp.moveaxis(x, 1, 2).reshape(b, hkv, h // hkv, t, d)
    return _fit(x, 3, t, t_pad, copies)


def _result(x, t: int, copies: int = 1):
    """A kernel's result, laid as ``_operand`` lays it, as [B, T, h, D]."""
    b, hkv, members, _, d = x.shape
    return jnp.moveaxis(_fit(x, 3, t, t, copies).reshape(
        b, hkv * members, t, d), 1, 2)


def _heads_of(q, k):
    """(B, T, H, D, Tk, Hkv, whether they came laid) of a call's q and k:
    [B, T, heads, D], or laid [B, Hkv, members, T, D]."""
    if q.ndim == 5:
        b, hkv, group, t, d = q.shape
        return b, t, hkv * group, d, k.shape[3], hkv, True
    b, t, h, d = q.shape
    return b, t, h, d, k.shape[1], k.shape[2], False


def _block_view(rows: int, members: int, d: int):
    """A block's shape inside a kernel: what ``_member`` indexes."""
    return (rows, d) if members == 1 else (members, rows, d)


def _operand_spec(rows: int, members: int, d: int, index):
    """The BlockSpec of ``rows`` positions of one key-value head's
    ``members``; ``index(*grid)`` gives (batch, key-value head, first
    position): an element offset, so that a window's steps can start where
    its band does."""
    return pl.BlockSpec(
        (None, None, None if members == 1 else pl.Element(members),
         pl.Element(rows), pl.Element(d)),
        lambda *g: (lambda b_, hk, r: (b_, hk, 0, r, 0))(*index(*g)))


def _row_spec(rows: int, group: int, index):
    """The BlockSpec of a [B, Hkv, T, G] float32 row statistic."""
    return pl.BlockSpec(
        (None, None, pl.Element(rows), pl.Element(group)),
        lambda *g: (lambda b_, hk, r: (b_, hk, r, 0))(*index(*g)))


# A group's tiles are wide (eight members' q, o, dO blocks and one (bq, bk)
# float32 tile each for S, P, dP, dS): a program may hold 32 MiB of the
# chip's 128 MiB of VMEM, twice what a kernel is given by default.
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 * 2**20)


def _flash_forward(q, k, v, mask, blocks, interpret):
    b, t, h, d, tk, hkv, _ = _heads_of(q, k)
    group = h // hkv
    band = mask.band(blocks[0], blocks[1], t, tk)

    def at_q(b_, hk, iq, s):
        return b_, hk, iq * band.bq

    def at_k(b_, hk, iq, s):
        return b_, hk, band.step(iq, s)[2]

    q_spec = _operand_spec(band.bq, group, d, at_q)
    kv_spec = _operand_spec(band.bk, 1, d, at_k)
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, band=band, group=group,
                          scale=1.0 / (d ** 0.5)),
        grid=(b, hkv, band.n, band.steps),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, _row_spec(band.bq, group, at_q)],
        out_shape=[
            jax.ShapeDtypeStruct(
                (b, hkv, group, band.tq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, band.tq_pad, group), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM(_block_view(band.bq, group, d), q.dtype),
            pltpu.VMEM((band.bq, _LANES), jnp.float32),
            pltpu.VMEM((band.bq, _LANES), jnp.float32),
            pltpu.VMEM((group, band.bq, d), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS,
        # two products (S, P V); q, k, v in (k and v at their own head
        # count: less than the two arrays claimed), o and the logsumexp out
        cost_estimate=_stream_cost(band, 2, b, h, d, q.dtype.itemsize,
                                   arrays=4, rows=1),
        interpret=interpret,
    )(_operand(q, hkv, band.tq_pad, band.copies),
      _operand(k, hkv, band.tk_pad, band.copies),
      _operand(v, hkv, band.tk_pad, band.copies))
    return _result(out, t, band.copies), lse


def _masked_scores(s, row0, col0, *, keys_axis: int = 1, **mask):
    """Scores with what ``_valid`` refuses at -1e30 (the whole-sequence
    kernels' one tile)."""
    valid = _valid(s.shape, row0, col0, keys_axis=keys_axis, **mask)
    return s if valid is None else jnp.where(valid, s, NEG_INF)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                   delta_ref, qs_scr, dq_scr, dl_scr, *, band: _Band,
                   group: int, scale: float):
    """dQ pass: parallel over q blocks, the keys stream sequentially.

    The group's (G, block_q, d) dQ tiles accumulate in fp32 scratch across
    the k stream; the temperature (folded out of dS) is applied once per
    tile in the epilogue instead of once per (bq, bk) score tile. The pass
    also takes ``delta = rowsum(dO ∘ O)`` of its rows, once a q block, from
    the blocks it holds (no float32 copy of dO or O is made in HBM for it),
    and hands it to the dKV pass."""
    row0 = pl.program_id(2) * band.bq
    step = pl.program_id(3)
    col0, run, _ = band.step(pl.program_id(2), step)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        qs_scr[...] = _scaled(q_ref[...], scale)
        for j in range(group):
            dl_scr[:, j:j + 1] = jnp.sum(
                _member(do_ref, j)[...].astype(jnp.float32)
                * _member(o_ref, j)[...].astype(jnp.float32),
                axis=1, keepdims=True)                      # (bq, 1)
        delta_ref[...] = dl_scr[:, :group]

    def _step(valid, rows=None, keys=None, looped=False):
        (qr, r), (kr, _) = _whole(rows), _whole(keys)
        k = k_ref[kr]                                       # (bk, d)
        v = v_ref[kr]                                       # (bk, d)

        def member(j):
            q = _member(qs_scr, j)[qr]                   # (bq, d), scaled
            do = _member(do_ref, j)[qr]                  # (bq, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bq, bk)
            if valid is not None:
                s = jnp.where(valid, s, NEG_INF)
            # p from the saved statistics — no second softmax pass.
            p = jnp.exp(s - _column(lse_ref, r, j))         # (bq, bk)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bq, bk)
            ds = p * (dp - _column(dl_scr, r, j))           # (bq, bk)
            dq_scr.at[j][qr] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bq, d)

        _members(group, member, looped)

    _on_band(band, row0, col0, run, _step)

    @pl.when(step == band.steps - 1)
    def _finish():
        for j in range(group):
            _member(dq_ref, j)[...] = (dq_scr[j] * scale).astype(
                dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, ks_scr, dk_scr, dv_scr, *, band: _Band,
                    group: int, scale: float):
    """dKV pass: parallel over KV blocks; the q rows of the group stream
    sequentially.

    Each program owns one (block_k, d) dK tile and one dV tile in fp32
    scratch and streams Q/dO past them, of every query head that reads this
    key-value head: the group's sum is taken where the tiles lie.
    Everything stays (bq, bk)-oriented —
    probabilities are transposed only implicitly, by contracting over the q
    dim in the two gradient matmuls. (A materialized (1, bq) lse/delta row
    would need a sublane→lane relayout that Mosaic can't lower; a (bq, 1)
    column is native.) The temperature rides on the side that stays: K is
    scaled once a program into scratch (S = Q·(scale·K)ᵀ, no rescale of
    every q block that streams past), and dK = scale·dSᵀ·Q takes it once
    more in the epilogue."""
    col0 = pl.program_id(2) * band.bk
    step = pl.program_id(3)
    row0, run, _ = band.step(pl.program_id(2), step)

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        ks_scr[...] = _scaled(k_ref[...], scale)

    def _step(valid, rows=None, keys=None, looped=False):
        # ``looped`` is a lone square's, and only a pass that streams the
        # keys states one: here the members are always unrolled
        (qr, r), (kr, _) = _whole(rows), _whole(keys)
        k = ks_scr[kr]                                      # (bk, d), scaled
        v = v_ref[kr]                                       # (bk, d)
        dk = dv = 0.0
        for j in range(group):
            q = _member(q_ref, j)[qr]                    # (bq, d)
            do = _member(do_ref, j)[qr]                  # (bq, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bq, bk)
            if valid is not None:
                s = jnp.where(valid, s, NEG_INF)
            p = jnp.exp(s - lse_ref[r, j:j + 1])            # (bq, bk)
            dv += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bk, d)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bq, bk)
            ds = p * (dp - delta_ref[r, j:j + 1])           # (bq, bk)
            dk += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (bk, d)
        dk_scr[kr] += dk
        dv_scr[kr] += dv

    _on_band(band, row0, col0, run, _step)

    @pl.when(step == band.steps - 1)
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _rows(x, t: int, t_pad: int, copies: int = 1):
    """A [B, Hkv, T', G] row statistic at another pass's padded length."""
    return _fit(x, 2, t, t_pad, copies)


def _flash_backward(q, k, v, o, lse, g, mask, blocks_dq, blocks_dkv,
                    interpret):
    """Two-pass flash backward (see module docstring): a dQ pass parallel
    over q blocks, which also takes ``delta = rowsum(dO ∘ O)``, and a dKV
    pass parallel over KV blocks, sharing the saved ``lse`` and delta. For
    q and k that came laid (``flash_attention_laid``: neither pass pads a
    row) dQ and dK are handed back as the kernels write them."""
    b, t, h, d, tk, hkv, laid = _heads_of(q, k)
    group = h // hkv
    scale = 1.0 / (d ** 0.5)
    isz = q.dtype.itemsize
    # Both per-row statistics ride as (B, Hkv, Tq, G), as the forward wrote
    # the logsumexp, padded to the FORWARD's q-block multiple: each pass
    # re-pads from the true length. Fully-masked (padded) q rows carry lse =
    # NEG_INF; exp(s - NEG_INF) would overflow to inf → NaN via inf·0 in the
    # matmuls, so clamp those rows to 0 — with the clamp their contributions
    # cancel exactly (zero dO/delta rows), which is why the backward kernels
    # need no q-row mask.
    band = mask.band(blocks_dq[0], blocks_dq[1], t, tk)
    copies = band.copies
    lse = _rows(lse, t, t, copies)
    lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)

    def at_q(b_, hk, iq, s):
        return b_, hk, iq * band.bq

    def at_k(b_, hk, iq, s):
        return b_, hk, band.step(iq, s)[2]

    q_spec = _operand_spec(band.bq, group, d, at_q)
    kv_spec = _operand_spec(band.bk, 1, d, at_k)
    row_spec = _row_spec(band.bq, group, at_q)
    qt, ot, dot = (_operand(x, hkv, band.tq_pad, copies) for x in (q, o, g))
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, band=band, group=group,
                          scale=scale),
        grid=(b, hkv, band.n, band.steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(
                (b, hkv, group, band.tq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, band.tq_pad, group), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM(_block_view(band.bq, group, d), q.dtype),
                        pltpu.VMEM((group, band.bq, d), jnp.float32),
                        pltpu.VMEM((band.bq, _LANES), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        # the model's work: dP and dQ (the recomputed scores are left out,
        # as the whole-sequence backward leaves them); q, k, v, o, dO in,
        # dQ out, the logsumexp in and delta out
        cost_estimate=_stream_cost(band, 2, b, h, d, isz, arrays=6, rows=2),
        interpret=interpret,
    )(qt, _operand(k, hkv, band.tk_pad, copies),
      _operand(v, hkv, band.tk_pad, copies), ot, dot,
      _rows(lse, t, band.tq_pad, copies))
    if not laid:
        dq = _result(dq, t, copies)

    # dKV: one program a key-value head and k block; the q rows in its band
    # stream past it, the whole group in each step
    band = mask.band(blocks_dkv[0], blocks_dkv[1], t, tk, stream="q")

    def own_k(b_, hk, ik, s):
        return b_, hk, ik * band.bk

    def band_q(b_, hk, ik, s):
        return b_, hk, band.step(ik, s)[2]

    k_spec = _operand_spec(band.bk, 1, d, own_k)
    q_spec = _operand_spec(band.bq, group, d, band_q)
    row_spec = _row_spec(band.bq, group, band_q)
    kv_shape = jax.ShapeDtypeStruct(
        (b, hkv, 1, band.tk_pad, d), k.dtype)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, band=band, group=group,
                          scale=scale),
        grid=(b, hkv, band.n, band.steps),
        in_specs=[k_spec, k_spec, q_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[kv_shape, kv_shape],
        scratch_shapes=[pltpu.VMEM((band.bk, d), k.dtype),
                        pltpu.VMEM((band.bk, d), jnp.float32),
                        pltpu.VMEM((band.bk, d), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        # the model's work: dV and dK
        cost_estimate=_stream_cost(band, 2, b, h, d, isz, arrays=6, rows=2),
        interpret=interpret,
    )(_operand(k, hkv, band.tk_pad, copies),
      _operand(v, hkv, band.tk_pad, copies),
      _operand(q, hkv, band.tq_pad, copies),
      _operand(g, hkv, band.tq_pad, copies),
      _rows(lse, t, band.tq_pad, copies), _rows(delta, t, band.tq_pad, copies))
    return (dq, dk if laid else _result(dk, tk, copies),
            _result(dv, tk, copies))


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


def program_plan(seq: int, heads: int, head_dim: int, dtype, *,
                 kv_heads: int | None = None, causal: bool = False,
                 window: int | None = None,
                 block_diffusion: tuple | None = None,
                 fused: bool = False) -> dict:
    """How far the kernels engage at a static self-attention shape, for the
    dispatch line and its telemetry event: the ``schedule`` (the fused entry
    chooses; split operands stream), the query heads a program holds, its
    blocks (the forward's), and ``band_fill`` = scores the mask allows over
    scores the forward's programs run. Under ``block_diffusion = (L,
    block)`` ``seq`` is the ``2 L`` positions of the doubled row."""
    kv_heads = kv_heads or heads
    if (fused and kv_heads == heads and window is None
            and block_diffusion is None
            and schedule_for(seq, heads, head_dim, dtype) == WHOLE_SEQ):
        fill = (seq + 1) / (2 * seq) if causal else 1.0
        return {"schedule": WHOLE_SEQ, "heads_per_program": _head_group(
                    seq, heads, head_dim, jnp.dtype(dtype).itemsize),
                "block_q": seq, "block_k": seq, "band_fill": round(fill, 4)}
    group = heads // kv_heads
    group //= _programs_of(group)
    block_q, block_k = _default_blocks(seq, seq, window, group).fwd
    band = _Mask(causal, window, block_diffusion).band(
        block_q, block_k, seq, seq)
    plan = {"schedule": STREAMING, "heads_per_program": group,
            "block_q": band.bq, "block_k": band.bk,
            "band_fill": round(band.fill(), 4)}
    if block_diffusion is not None:
        plan.update(mask="block_diffusion", block_length=block_diffusion[1])
    return plan


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
    return _masked_scores(st, 0, 0, causal=causal, q_len=t, k_len=t,
                          mask_k=False, keys_axis=0)


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
