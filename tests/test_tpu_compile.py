"""Compiles for the chip, without the chip.

The TPU compiler installed beside jax compiles for a *described* v5e
topology — no device attached, nothing runs — and raises exactly what the
chip's compiler would raise. Interpret mode checks none of this: it never
looks at Mosaic's tiling rules, its supported ops or VMEM limits, which is
how a kernel backward that 21 interpret-mode tests passed was refused at
every width on the real target (PR 21).

- the Pallas kernels at real widths, forward and forward+backward (tier-1,
  well under a second each), the chunked scan's pair among them;
- the whole train-step programs the chip smoke runs (``slow``): resnet18
  on one and on four described chips, ViT-B/16 with flash on one chip and
  dp x tp over the 2x2.

Model code that asks ``jax.default_backend()`` (``interpret=None`` in the
kernels) would take its CPU branch here, so the whole-step tests steer it
with a monkeypatch — in the test, not through an option of the program.
A compile that passes is not a chip run: ``chip_smoke.py`` is that.
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# libtpu lets one process per host load it (a lockfile under /tmp) — the
# rule that gives a chip to one process. Describing a topology attaches
# nothing, and under pytest-xdist several workers reach this module at
# once: without this every worker but the first would skip.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

BATCH = 128                              # resnet18 @224, per chip
FLASH_SHAPES = ((128, 197, 12, 64),      # ViT-B/16 @224
                (8, 2048, 12, 64))       # exact-tiling long sequence
# The fused [B, T, H, 3, D] entry (batch, tokens, heads, head_dim, causal):
# ViT-B/16 on one chip and its tp=2 shard, then the whole-sequence
# schedule's rule at its edge (256 tiles exactly; 577 is ViT-B/16 @384;
# 640 is the longest that fits, at two heads a program), full and causal,
# and the first length past it, which streams.
# The streaming schedule at a decoder's shape (batch, tokens, query heads,
# key-value heads, head_dim, window): two sequences of 8,192 with 32 heads
# over 4 of 128, a sliding-window layer and a full one.
# then a group of sixteen query heads (32 over 2), split over two programs
# of eight, and a group of one (16 over 16, one row: the looped cell's)
FLASH_GQA_SHAPES = ((2, 8192, 32, 4, 128, 1024), (2, 8192, 32, 4, 128, None),
                    (2, 8192, 32, 2, 128, None), (1, 8192, 16, 16, 128, None))
# The same schedule under the mask of training by diffusion over blocks
# (batch, positions of the doubled row, query heads, key-value heads,
# head_dim, block length): two rows of 8,192 ids, each a noised and a
# clean copy.
FLASH_BD_SHAPES = ((2, 16384, 32, 4, 128, 4),)
SSD_SCAN_SHAPES = ((2, 8192, 64, 64, 8, 128, 128),
                   (1, 1000, 64, 64, 8, 128, 128))
# q / k RMSNorm and RoPE as one pass (rows, positions, heads, key-value
# heads, head_dim): the two claimed cells' shapes
QK_NORM_ROPE_SHAPES = ((2, 8192, 32, 4, 128), (2, 16384, 32, 4, 128))
# the same pass with a rotation and no norm, at a group of one (the looped
# cell's: one row, 16 heads over 16)
QK_ROPE_SHAPES = ((1, 8192, 16, 16, 128),)
# the Mamba-2 mixer's convolution pass (rows, positions, the columns of
# in_proj's result, the widths of x, B and C, taps, xBC's first column): the
# hybrid cell's shape
CAUSAL_CONV_SHAPES = ((2, 8192, 10304, (4096, 1024, 1024), 4, 4096),)
# latent attention's entry (rows, positions, heads, the keys' unrotated and
# rotated columns, the values' columns): queries and keys of 192 against
# values of 128, the rotated key one head for all
FLASH_LATENT_SHAPES = ((2, 8192, 32, 128, 64, 128),)
# the same with the operands where the projections and the rotation's pass
# wrote them, and that pass (.., the latent rank behind which kv_a_proj wrote
# the rotated key): the latent cell's shape
LATENT_LAID_SHAPES = ((2, 8192, 32, 128, 64, 128, 512),)
FLASH_QKV_SHAPES = ((128, 197, 12, 64, False), (256, 197, 6, 64, False),
                    (64, 256, 12, 64, False), (64, 256, 12, 64, True),
                    (32, 577, 12, 64, False), (32, 577, 12, 64, True),
                    (32, 640, 12, 64, False), (32, 640, 12, 64, True),
                    (32, 704, 12, 64, False))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu / unknown topology name
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip (the next run would warn and
    recompile), so the cache is off around this module."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _flash_fn(bwd: bool):
    from tpudist.ops.pallas.flash_attention import flash_attention

    def f(q, k, v):
        return flash_attention(q, k, v,
                               interpret=False).astype(jnp.float32).sum()

    return jax.grad(f, argnums=(0, 1, 2)) if bwd else f


def _flash_gqa_fn(bwd: bool, window):
    from tpudist.ops.pallas.flash_attention import flash_attention

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False).astype(jnp.float32).sum()

    return jax.grad(f, argnums=(0, 1, 2)) if bwd else f


def _flash_bd_fn(bwd: bool, length: int, block: int):
    from tpudist.ops.pallas.flash_attention import flash_attention

    def f(q, k, v):
        return flash_attention(q, k, v, block_diffusion=(length, block),
                               interpret=False).astype(jnp.float32).sum()

    return jax.grad(f, argnums=(0, 1, 2)) if bwd else f


def _grouped_fn(bwd: bool):
    from tpudist.ops.pallas.grouped_matmul import grouped_matmul

    def f(x, w, sizes):
        return grouped_matmul(x, w, sizes,
                              interpret=False).astype(jnp.float32).sum()

    return jax.grad(f, argnums=(0, 1)) if bwd else f


def _ssd_scan_fn(bwd: bool, chunk: int):
    """``ssd.ssd_scan`` as the mixer calls it (the test steers
    ``jax.default_backend``, which decides whether the pair is compiled)."""
    from tpudist.ops import ssd

    def f(x, dt, a, b, c, d):
        return ssd.ssd_scan(x, dt, a, b, c, d, chunk)[0].sum()

    return jax.grad(f, argnums=tuple(range(6))) if bwd else f


def _qk_norm_rope_fn(bwd: bool, t: int, d: int, kv_heads: int,
                     norm: bool = True):
    from tpudist.ops import rope
    from tpudist.ops.pallas.qk_norm_rope import qk_norm_rope
    cos, sin = rope.tables({"rope_type": "default", "rope_theta": 1e6}, d, t)

    def f(q, k, q_scale=None, k_scale=None):
        ql, kl = qk_norm_rope(q, k, kv_heads=kv_heads, q_scale=q_scale,
                              k_scale=k_scale, cos=cos, sin=sin,
                              interpret=False)
        return ql.astype(jnp.float32).sum() + kl.astype(jnp.float32).sum()

    return jax.grad(f, argnums=tuple(range(4 if norm else 2))) if bwd else f


def _causal_conv_fn(bwd: bool, offset: int, widths: tuple):
    from tpudist.ops import ssd

    def f(src, kernel, bias):
        return sum(jnp.square(v.astype(jnp.float32)).sum()
                   for v in ssd.conv_silu_split(src, kernel, bias, offset,
                                                widths))

    return jax.grad(f, argnums=(0, 1, 2)) if bwd else f


def _flash_latent_fn(bwd: bool):
    from tpudist.ops.pallas import flash_attention_latent

    def f(*operands):
        return flash_attention_latent(
            *operands, interpret=False).astype(jnp.float32).sum()

    return jax.grad(f, argnums=(0, 1, 2, 3, 4)) if bwd else f


def _flash_latent_laid_fn(bwd: bool):
    from tpudist.ops.pallas import flash_attention_latent_laid

    def f(*operands):
        return flash_attention_latent_laid(
            *operands, interpret=False).astype(jnp.float32).sum()

    return jax.grad(f, argnums=(0, 1, 2, 3)) if bwd else f


def _latent_rope_fn(bwd: bool, t: int, heads: int, dr: int, kv_rank: int):
    from tpudist.ops import rope
    from tpudist.ops.pallas.latent_rope import latent_rope
    cos, sin = rope.tables({"rope_theta": 32000000.0}, dr, t)

    def f(q, kva):
        # (squares: a sum's cotangents are constants, and the jitted
        # backward would then run where it is traced)
        return sum(jnp.square(x.astype(jnp.float32)).sum()
                   for x in latent_rope(q, kva, cos, sin, heads=heads,
                                        kv_rank=kv_rank, interpret=False))

    return jax.grad(f, argnums=(0, 1)) if bwd else f


def _flash_qkv_fn(bwd: bool, causal: bool):
    from tpudist.ops.pallas.flash_attention import flash_attention_qkv

    def f(qkv):
        return flash_attention_qkv(
            qkv, causal=causal, interpret=False).astype(jnp.float32).sum()

    return jax.grad(f) if bwd else f


_KERNEL_CASES = (
    [pytest.param(("flash",) + shape, bwd,
                    id=f"flash_b{shape[0]}_t{shape[1]}_"
                       f"{'fwdbwd' if bwd else 'fwd'}")
       for shape in FLASH_SHAPES for bwd in (False, True)]
    + [pytest.param(("flash_gqa",) + shape, bwd,
                    id=f"flash_gqa_t{shape[1]}_h{shape[2]}_kv{shape[3]}_"
                       f"{'w%d_' % shape[5] if shape[5] else ''}"
                       f"{'fwdbwd' if bwd else 'fwd'}")
       for shape in FLASH_GQA_SHAPES for bwd in (False, True)]
    + [pytest.param(("flash_bd",) + shape, bwd,
                    id=f"flash_bd_t{shape[1]}_h{shape[2]}_kv{shape[3]}_"
                       f"bl{shape[5]}_{'fwdbwd' if bwd else 'fwd'}")
       for shape in FLASH_BD_SHAPES for bwd in (False, True)]
    # the grouped products of an expert layer's pair buffer: 131,072 rows
    # (the worst case of two sequences of 8,192 with 8 experts a token),
    # 16 experts held, hidden 2,304 <-> expert width 896, both directions
    + [pytest.param(("grouped", 131072, 16, k, n), bwd,
                    id=f"grouped_{k}x{n}_{'fwdbwd' if bwd else 'fwd'}")
       for k, n in ((2304, 896), (896, 2304)) for bwd in (False, True)]
    # an expert width that is no whole number of lane tiles (1,856 = 14.5 x
    # 128; 98,304 rows, 8 experts held): tiles of 640 overhang its end
    + [pytest.param(("grouped", 98304, 8, k, n), bwd,
                    id=f"grouped_{k}x{n}_{'fwdbwd' if bwd else 'fwd'}")
       for k, n in ((2688, 1856), (1856, 2688)) for bwd in (False, True)]
    # the Mamba-2 chunked scan at the published shape (rows, positions,
    # heads, head_dim, groups, state, chunk): two rows of 8,192, then a
    # length the chunk does not divide
    + [pytest.param(("ssd_scan",) + shape, bwd,
                    id=f"ssd_scan_t{shape[1]}_{'fwdbwd' if bwd else 'fwd'}")
       for shape in SSD_SCAN_SHAPES for bwd in (False, True)]
    + [pytest.param(("qk_norm_rope",) + shape, bwd,
                    id=f"qk_norm_rope_t{shape[1]}_"
                       f"{'fwdbwd' if bwd else 'fwd'}")
       for shape in QK_NORM_ROPE_SHAPES for bwd in (False, True)]
    + [pytest.param(("qk_rope",) + shape, bwd,
                    id=f"qk_rope_t{shape[1]}_h{shape[2]}_kv{shape[3]}_"
                       f"{'fwdbwd' if bwd else 'fwd'}")
       for shape in QK_ROPE_SHAPES for bwd in (False, True)]
    + [pytest.param(("causal_conv",) + shape, bwd,
                    id=f"causal_conv_t{shape[1]}_"
                       f"{'fwdbwd' if bwd else 'fwd'}")
       for shape in CAUSAL_CONV_SHAPES for bwd in (False, True)]
    + [pytest.param(("flash_latent",) + shape, bwd,
                    id=f"flash_latent_t{shape[1]}_h{shape[2]}_"
                       f"{'fwdbwd' if bwd else 'fwd'}")
       for shape in FLASH_LATENT_SHAPES for bwd in (False, True)]
    + [pytest.param((kind,) + shape, bwd,
                    id=f"{kind}_t{shape[1]}_h{shape[2]}_"
                       f"{'fwdbwd' if bwd else 'fwd'}")
       for kind in ("flash_latent_laid", "latent_rope")
       for shape in LATENT_LAID_SHAPES for bwd in (False, True)]
    + [pytest.param(("flash_qkv",) + shape, bwd,
                    id=f"flash_qkv_b{shape[0]}_t{shape[1]}_h{shape[2]}_"
                       f"{'causal_' if shape[4] else ''}"
                       f"{'fwdbwd' if bwd else 'fwd'}")
       for shape in FLASH_QKV_SHAPES for bwd in (False, True)])


@pytest.mark.parametrize("case,bwd", _KERNEL_CASES)
def test_kernel_compiles_for_v5e(topo, monkeypatch, case, bwd):
    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    if case[0] == "flash_qkv":
        b, t, h, d, causal = case[1:]
        args = [S((b, t, h, 3, d), jnp.bfloat16)]
        fn = _flash_qkv_fn(bwd, causal)
    elif case[0] == "flash_latent":
        b, t, h, dn, dr, dv = case[1:]
        args = [S((b, t, h, dn), jnp.bfloat16), S((b, t, h, dr), jnp.bfloat16),
                S((b, t, h, dn), jnp.bfloat16), S((b, t, dr), jnp.bfloat16),
                S((b, t, h, dv), jnp.bfloat16)]
        fn = _flash_latent_fn(bwd)
    elif case[0] == "flash_latent_laid":
        b, t, h, dn, dr, dv, _ = case[1:]
        args = [S((b, h, t, dn), jnp.bfloat16), S((b, h, t, dr), jnp.bfloat16),
                S((b, t, h * (dn + dv)), jnp.bfloat16),
                S((b, t, dr), jnp.bfloat16)]
        fn = _flash_latent_laid_fn(bwd)
    elif case[0] == "latent_rope":
        b, t, h, dn, dr, dv, rank = case[1:]
        args = [S((b, t, h * (dn + dr)), jnp.bfloat16),
                S((b, t, rank + dr), jnp.bfloat16)]
        fn = _latent_rope_fn(bwd, t, h, dr, rank)
    elif case[0] == "ssd_scan":
        b, t, h, p, g, n, chunk = case[1:]
        args = [S((b, t, h, p), jnp.bfloat16), S((b, t, h), jnp.float32),
                S((h,), jnp.float32), S((b, t, g, n), jnp.bfloat16),
                S((b, t, g, n), jnp.bfloat16), S((h,), jnp.float32)]
        fn = _ssd_scan_fn(bwd, chunk)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    elif case[0] in ("qk_norm_rope", "qk_rope"):
        b, t, h, hkv, d = case[1:]
        norm = case[0] == "qk_norm_rope"
        args = [S((b, t, h * d), jnp.bfloat16), S((b, t, hkv * d),
                                                  jnp.bfloat16)] + [
            S((d,), jnp.float32)] * (2 * norm)
        fn = _qk_norm_rope_fn(bwd, t, d, hkv, norm)
    elif case[0] == "causal_conv":
        b, t, columns, widths, taps, offset = case[1:]
        args = [S((b, t, columns), jnp.bfloat16),
                S((taps, sum(widths)), jnp.float32),
                S((sum(widths),), jnp.float32)]
        fn = _causal_conv_fn(bwd, offset, widths)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    elif case[0] == "grouped":
        _, rows, groups, k, n = case
        args = [S((rows, k), jnp.bfloat16), S((groups, k, n), jnp.bfloat16),
                S((groups,), jnp.int32)]
        fn = _grouped_fn(bwd)
    elif case[0] in ("flash_gqa", "flash_bd"):
        b, t, h, hkv, d, window = case[1:]
        args = [S((b, t, h, d), jnp.bfloat16)] + [
            S((b, t, hkv, d), jnp.bfloat16)] * 2
        fn = (_flash_gqa_fn(bwd, window) if case[0] == "flash_gqa"
              else _flash_bd_fn(bwd, t // 2, window))
    else:
        args = [S(case[1:], jnp.bfloat16)] * 3
        fn = _flash_fn(bwd)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if case[0] == "flash_latent":
        # forward, dQ and dKV kernels; what they claim is the products over
        # the keys' true 192 columns and the values' 128 on the tiles that
        # run (blocks of 1,024: 36 of a head's 64), between the pairs the
        # mask allows and an eighth more; no [B, T, H, 192] key is built
        # (the rotated key stays [B, T, 64]) and the row statistics lie on
        # the lanes: no [..., T, 1] float32 column, 128 x its bytes on the chip
        import re
        assert text.count("tpu_custom_call") == (3 if bwd else 1)
        allowed = t * (t + 1) // 2
        least = (3 if bwd else 1) * 2 * b * h * allowed * (dn + dr + dv)
        assert least <= compiled.cost_analysis()["flops"] <= 1.15 * least
        assert not re.search(rf"\[{b},(?:{t},{h}|{h},{t}),{dn + dr}\]", text)
        assert not re.search(rf"f32\[[\d,]*{t},1\]", text)
        assert f"f32[{b},{h},1,{t}]" in text
        return
    if case[0] == "flash_latent_laid":
        # the same three kernels and the same claims; nothing is moved
        # around them: k_nope and v are read and their cotangents written
        # as one block of kv_b_proj's columns, o and dO by column block
        import re
        assert text.count("tpu_custom_call") == (3 if bwd else 1)
        allowed = t * (t + 1) // 2
        least = (3 if bwd else 1) * 2 * b * h * allowed * (dn + dr + dv)
        assert least <= compiled.cost_analysis()["flops"] <= 1.15 * least
        assert not re.search(r" transpose\(| pad\(| concatenate\(", text)
        assert f"bf16[{b},{t},{h * dv}]" in text
        if bwd:
            assert f"bf16[{b},{t},{h * (dn + dv)}]" in text
        return
    if case[0] == "latent_rope":
        # the forward and (a square's gradient reads the results) the
        # backward. Nothing is transposed, sliced or joined around them: q
        # arrives as q_b_proj wrote it and leaves as the attention kernels
        # read it, the rotated key is read out of kv_a_proj's result. What
        # a call claims to move is q and the rotated key twice, and the
        # tables
        import re
        assert text.count("tpu_custom_call") == (2 if bwd else 1)
        assert not re.search(r" transpose\(| concatenate\(| slice\(", text)
        moved = 2 * b * t * (h * (dn + dr) + dr) * 2 + 8 * t * dr
        assert text.count(f'"bytes_accessed":"{moved}"') == (
            2 if bwd else 1)
        return
    if case[0] == "ssd_scan":
        # the forward alone; or the forward that keeps the entering states
        # and the backward. No [.., Q, Q] decay of a head is in the program
        # outside them, and what the kernels claim is the four products
        # (backward: each one's two transposes and the two recomputed)
        import re
        assert text.count("tpu_custom_call") == (2 if bwd else 1)
        assert not re.search(rf"\[[\d,]*{h},{chunk},{chunk}\]", text)
        products = 2 * b * -(-t // chunk) * chunk * (
            g * chunk * n + h * (chunk * p + 2 * p * n))
        flops = compiled.cost_analysis()["flops"]
        assert products * (3.3 if bwd else 1) <= flops <= products * (
            3.6 if bwd else 1.1)
        return
    if case[0] == "qk_norm_rope":
        # the forward; or the backward alone (a sum's gradient needs no
        # forward). Nothing is transposed around them: q and k arrive as
        # the projections wrote them and leave as the attention kernels
        # read them. What the calls claim to move is q and k twice
        # (forward) or three times, and the tables
        import re
        assert text.count("tpu_custom_call") == 1
        assert not re.search(r" transpose\(| copy\(", text)
        moved = (3 if bwd else 2) * b * t * (h + hkv) * d * 2 + 8 * t * d
        assert compiled.cost_analysis()["bytes accessed"] >= moved
        assert f'"bytes_accessed":"{moved}"' in text
        return
    if case[0] == "causal_conv":
        # the forward; or the forward and the backward (the squares' gradient
        # reads the results). xBC is read where it lies: no slice of the
        # source's columns and no split of the result is in the program,
        # and what the calls claim to move is x, B and C twice or thrice
        import re
        assert text.count("tpu_custom_call") == (2 if bwd else 1)
        assert not re.search(rf"bf16\[{b},{t},{sum(widths)}\]\S* "
                             rf"(?:slice|copy|fusion)\(", text)
        for arrays in (2, 3)[:1 + bwd]:
            moved = arrays * b * t * sum(widths) * 2
            assert f'"bytes_accessed":"{moved}"' in text
        return
    if case[0] == "grouped":
        # the product; or its two transposes (dx, dw: a sum's gradient
        # needs no forward)
        assert text.count("tpu_custom_call") == (2 if bwd else 1)
        return
    if case[0] == "flash_bd":
        # the same three kernels; what they claim lies between the pairs the
        # mask allows, L (L + block) a head and a row, and an eighth more
        # (since rev 7 the squares that stand for an edge tile: a fill of
        # 0.889 forward, 0.928 dQ, 0.877 dKV, where whole tiles of 512 x
        # 1,024 gave 0.80)
        assert text.count("tpu_custom_call") == (3 if bwd else 1)
        length, block = t // 2, window
        least = (6 if bwd else 2) * 2 * b * h * length * (length + block) * d
        assert least <= compiled.cost_analysis()["flops"] <= 1.15 * least
        return
    if case[0] == "flash_gqa":
        # forward, dQ and dKV kernels; what they claim is the blocks inside
        # the band (two products forward, four more backward), not the
        # square's: between the pairs the mask allows and little over
        # twice that (blocks of 1,024 under a window of 1,024: two a q block)
        assert text.count("tpu_custom_call") == (3 if bwd else 1)
        allowed = (t * (t + 1) // 2 if window is None
                   else window * t - window * (window - 1) // 2)
        least = (6 if bwd else 2) * 2 * b * h * allowed * d
        assert least <= compiled.cost_analysis()["flops"] <= 2.1 * least
        return
    if case[0] == "flash_qkv":
        from tpudist.ops.pallas.flash_attention import (WHOLE_SEQ,
                                                        schedule_for)
        b, t, h, d, _ = case[1:]
        if schedule_for(t, h, d, jnp.bfloat16) != WHOLE_SEQ:
            # past the rule's edge: forward, dQ and dKV streaming kernels
            assert t == 704 and text.count("tpu_custom_call") == (
                3 if bwd else 1)
            return
        # one forward and one backward kernel, and what they cost is
        # counted (mfu_pct reads cost_analysis of the step)
        assert text.count("tpu_custom_call") == (2 if bwd else 1)
        flops = compiled.cost_analysis()["flops"]
        assert flops >= (12 if bwd else 4) * b * h * t * t * d


# -- a whole expert layer (slow: ~25 s of TPU compiler) ----------------------

@pytest.mark.slow
@pytest.mark.parametrize("held", [16, 64], ids=["quarter_held", "all_held"])
def test_expert_layer_compiles_for_v5e(topo, monkeypatch, held):
    """``moe_topk_held`` forward + backward at the decoder cell's shape (two
    sequences of 8,192, hidden 2,304, 64 experts of width 896, 8 a token;
    16 held as in the cell, and all 64: the share at which every block of
    the pair buffer is walked). The loops with a trip count on the device
    lower, nothing of [T, k, d] is built, and outside the grouped products
    no operation of the program's body runs over the buffer's 131,072 rows:
    they are allocated, and written by loops."""
    import re

    from tpudist.parallel.moe import moe_topk_held
    one = SingleDeviceSharding(topo.devices[0])
    t, d, f, experts, k = 16384, 2304, 896, 64, 8

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = {"router": S((d, experts)), "gate": S((held, d, f)),
              "up": S((held, d, f)), "down": S((held, f, d))}

    def loss(p, u):
        y, counters = moe_topk_held(p, u.astype(jnp.bfloat16), top_k=k,
                                    router_input=u)
        return jnp.sum(jnp.square(y.astype(jnp.float32))), counters

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        params, S((t, d))).compile()
    text = compiled.as_text()
    # two products forward; their four transposes
    assert text.count("tpu_custom_call") == 6
    assert f"[{t},{k},{d}]" not in text
    entry = text[text.index("\nENTRY "):]
    over_the_buffer = set(re.findall(
        rf"= (?:bf16|f32)\[{t * k},\d+\]\S* ([\w\-]+)\(", entry))
    assert over_the_buffer <= {"custom-call", "get-tuple-element", "bitcast"}
    # one layer's temporaries: 2.29 GB with a quarter held (2.66 before the
    # loops), and no more of the buffer's size with every expert held
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9


# -- whole train steps (slow: ~15-30 s of TPU compiler each) -----------------

def _abstract_state(model, cfg, mesh, specs_fn=None):
    from tpudist.train import create_train_state
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), model, cfg))
    specs = (specs_fn(state) if specs_fn is not None
             else jax.tree_util.tree_map(lambda _: P(), state))
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        state, specs)


def _compile_step(monkeypatch, step, state, cfg, mesh):
    images = jax.ShapeDtypeStruct(
        (cfg.batch_size, cfg.image_size, cfg.image_size, 3), jnp.float32,
        sharding=NamedSharding(mesh, P("data")))
    labels = jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    lr = jax.ShapeDtypeStruct((), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    # The kernels resolve interpret mode from the backend at trace time.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return step.lower(state, images, labels, lr).compile()


@pytest.mark.slow
@pytest.mark.parametrize("n_dev,sync_bn", [
    (1, False),             # the program chip_smoke's train phase runs
    (4, True),              # chip_smoke --multichip: DP + SyncBN
], ids=["1chip_xla", "4chip_syncbn"])
def test_resnet18_step_compiles_for_v5e(topo, monkeypatch, n_dev, sync_bn):
    from tpudist.config import Config
    from tpudist.models import create_model
    from tpudist.train import compute_dtype, make_train_step
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("data",))
    cfg = Config(arch="resnet18", num_classes=1000, image_size=224,
                 batch_size=BATCH * n_dev, use_amp=True,
                 sync_batchnorm=sync_bn, seed=0).finalize(n_dev)
    model = create_model("resnet18", num_classes=1000,
                         dtype=compute_dtype(cfg), sync_batchnorm=sync_bn,
                         bn_axis_name="data")
    compiled = _compile_step(
        monkeypatch, make_train_step(mesh, model, cfg),
        _abstract_state(model, cfg, mesh), cfg, mesh)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert ("all-reduce" in text) == (n_dev > 1)
    # Fits one v5e chip (16 GB) with room for the prefetch double buffer.
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 8 * 2**30


@pytest.mark.slow
@pytest.mark.parametrize("tp", [False, True], ids=["1chip", "dp2_tp2"])
def test_vit_b16_flash_step_compiles_for_v5e(topo, monkeypatch, tp):
    from tpudist.config import Config
    from tpudist.models import create_model
    from tpudist.train import compute_dtype, make_train_step
    model_kw = dict(num_classes=1000, flash=True)
    if tp:
        from tpudist.parallel import make_gspmd_train_step, plane
        mesh = Mesh(np.asarray(topo.devices).reshape(2, 2),
                    ("data", "model"))
        cfg = Config(arch="vit_b_16", num_classes=1000, image_size=224,
                     batch_size=128, use_amp=True, seed=0,
                     mesh_shape=[2, 2],
                     mesh_axes=["data", "model"]).finalize(4)
        model = create_model("vit_b_16", dtype=compute_dtype(cfg),
                             **model_kw)
        rules = plane.rules_for_mesh("vit_b_16", mesh)
        step = make_gspmd_train_step(mesh, model, cfg, rules,
                                     data_axis="data")
        state = _abstract_state(
            model, cfg, mesh,
            specs_fn=lambda s: plane.state_specs(mesh, s, rules))
    else:
        mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
        cfg = Config(arch="vit_b_16", num_classes=1000, image_size=224,
                     batch_size=64, use_amp=True, seed=0).finalize(1)
        model = create_model("vit_b_16", dtype=compute_dtype(cfg),
                             **model_kw)
        step = make_train_step(mesh, model, cfg)
        state = _abstract_state(model, cfg, mesh)
    text = _compile_step(monkeypatch, step, state, cfg, mesh).as_text()
    assert "tpu_custom_call" in text           # flash really is in the step
    assert ("all-reduce" in text) == tp


@pytest.mark.slow
@pytest.mark.parametrize("name,most_gib", [
    ("mellum2_12b_ep4", 11.2), ("sdar_30b_ep8", 14.5),
    ("nemotron3_nano_ep16", 15.0), ("ouro_2_6b_pp8", 15.0),
    ("joyai_flash_ep16", 15.0)])
def test_decoder_step_compiles_for_v5e(topo, monkeypatch, name, most_gib):
    """The whole step of a benchmark's decoder cell, as its configuration's
    ``trainer_argv`` builds it (its ``per_chip_batch`` rows of 8,192 ids,
    ``--remat``, the streaming attention kernel); its bytes are printed
    (``-s``).

    ``mellum2_12b_ep4`` (one chip's share of Mellum2-12B-A2.5B: 4 layers, 16
    of 64 experts, a quarter of the vocabulary): it fits, 11.04 GiB of the
    chip's 15.75 (11.80 before PR 33: four layers' logsumexp lay
    lane-padded, 268 MB each). ``sdar_30b_ep8`` (one chip's share of
    SDAR-30B-A3B-Chat, trained by diffusion over blocks: 4 layers, 16 of
    128 experts, an eighth of the vocabulary, each row a noised and a clean
    copy, 16,384 positions): it fits too, and no score tensor of the doubled
    row by itself or by a row is in the step. ``nemotron3_nano_ep16`` (one
    chip's share of Nemotron-3-Nano-30B-A3B: the first nine blocks, four of
    them Mamba-2 mixers by the chunked scan, one attention with a group of
    sixteen split over two programs of eight, 8 of 128 experts beside the
    shared one, an eighth of the vocabulary): it fits. ``ouro_2_6b_pp8`` (one
    pipeline stage's share of Ouro-2.6B: 6 dense layers run four times by a
    scan over the passes, one row): it fits, 13.15 GiB, with one pass's 18
    attention calls in the program (not 72), and its cost analysis counts
    the scan's body once (27.5 TFLOP of some 107).

    Inside each rematerialised layer no loop copies a pair buffer (a carry
    that XLA could not update in place cost 29 ms a step a loop, on the
    chip)."""
    import json
    import re

    from tpudist.config import from_args
    from tpudist.models import create_model
    from tpudist.train import compute_dtype, make_train_step
    from tpudist.trainer import _parse_share
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "chip", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    rows = config["per_chip_batch"]
    argv = [str(a).format(batch=rows, seed=0, outpath="unused")
            for a in config["trainer_argv"]]
    cfg = from_args(argv)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    model = create_model(cfg.arch, num_classes=cfg.num_classes,
                         dtype=compute_dtype(cfg), remat=True,
                         flash=True).clone(
        layers=cfg.layers,
        expert_share=_parse_share(cfg.expert_share, "--expert-share"),
        vocab_share=_parse_share(cfg.vocab_share, "--vocab-share"))
    ids = jax.ShapeDtypeStruct((rows, cfg.seq_len), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    lr = jax.ShapeDtypeStruct((), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the state is donated on the chip (conftest.py turns that off for the
    # CPU runtime): the outputs then lie where the arguments lay
    monkeypatch.delenv("TPUDIST_NO_DONATE", raising=False)
    compiled = make_train_step(mesh, model, cfg).lower(
        _abstract_state(model, cfg, mesh), ids, ids, lr).compile()
    ma = compiled.memory_analysis()
    step_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    print(f"{name}: step {step_bytes} bytes = {step_bytes / 2**30:.4f} GiB "
          f"(arguments {ma.argument_size_in_bytes}, temporaries "
          f"{ma.temp_size_in_bytes}, aliased {ma.alias_size_in_bytes}); "
          f"cost analysis {cost.get('flops')} flops")
    assert step_bytes < most_gib * 2**30
    text = compiled.as_text()
    diffusion = model.objective == "block_diffusion"
    positions = cfg.seq_len * (2 if diffusion else 1)
    pairs = 2 * positions * model.experts_per_token
    assert not re.search(rf"= (?:bf16|f32)\[{pairs},\d+\]\S* copy\(", text)
    # around the attention kernels (PR 33): the row statistics carry the
    # group on their minor dimension and delta is taken in the dQ pass, so
    # under ``attn_fused`` no [..., T, 1] float32 column (lane-padded 128 x
    # in HBM) is kept and XLA makes no float32 copy of q, o or dO
    under = [line for line in text.splitlines() if "/attn_fused/" in line]
    assert sum("tpu_custom_call" in line for line in under) == 3 * (len(
        [k for k in model.layer_types[:cfg.layers] if "attention" in k])
        + model.mtp_depth)
    for line in under:
        if model.latent:
            # the latent entry lays its statistics on the lanes: no [..., 1]
            # float32 column of a row's length at a group of one either
            assert not re.search(rf"f32\[[\d,]*{positions},1\]", line.split(
                " = ", 1)[-1].split("(", 1)[0]), line[:200]
            continue
        if model.num_heads == model.num_kv_heads:
            # a group of one IS a minor dimension of 1: the logsumexp and
            # delta of such a call lie lane-padded, 64 MiB each at 16 heads
            # of 8,192 positions (docs/ATTENTION.md, "A group of one")
            break
        made = line.split(" = ", 1)[-1].split("(", 1)[0]
        assert not re.search(rf"f32\[[\d,]*{positions},1\]", made), \
            line[:200]
    if diffusion:
        # no [.., 2L, 2L] and no [.., 2L, L] tensor anywhere in the step
        assert not re.search(
            rf"\[(?:\d+,)*{positions},(?:{positions}|{cfg.seq_len})\]", text)
