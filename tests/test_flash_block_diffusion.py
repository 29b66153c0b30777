"""The streaming attention kernels under the mask of training by diffusion
over blocks (``ops/pallas/flash_attention.py::_DiffusionBand``): forward and
the three gradients against the XLA ``attention`` in interpret mode, the
tiles that run against brute force, the stated cost, the plan at the cell's
shape, the dispatch key and event. A file of its own: the interpret-mode
cases of ``tests/test_flash_attention.py`` are already as many as one worker
process of this CPU runtime takes (tests/conftest.py says what it does after
a long session)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.pallas import flash_attention


def _diffusion(length, block, block_q, block_k, stream="k"):
    from tpudist.ops.pallas.flash_attention import _DiffusionBand
    return _DiffusionBand(length=length, block=block, block_q=block_q,
                          block_k=block_k, stream=stream)


@pytest.mark.parametrize("length,block,blocks,bwd,heads,kv_heads", [
    (32, 4, (8, 16), (None, None), 4, 2),      # a step wider than a q block
    (37, 4, (8, 8), (16, 8), 4, 2),            # ragged: 9 blocks and one id
    (40, 8, (16, 8), (8, 16), 2, 2),           # a q block wider than a step
    (30, 3, (8, 8), (None, None), 4, 1),       # blocks that are no power of 2
    (32, 4, (32, 16), (16, 32), 4, 1),         # one q block a copy
    (24, 4, (128, 128), (None, None), 2, 2),   # one tile holds both copies' own
    # rev 7, the squares that stand for an edge tile
    # (``_DiffusionBand.squares``). Keys twice the rows: q blocks on and off
    # a key block's boundary, one square of 256 keys in the forward and the
    # dQ pass (its members a loop on the device), the diagonal walked in
    # squares of 128 in the dKV pass; a group of 8
    (512, 4, (256, 512), (256, 512), 8, 1),
    # square tiles in the backward (the dQ pass's shape): the walk there too
    (512, 4, (256, 512), (256, 256), 2, 2),
    # a ragged length: squares of the walk past the last true row
    (600, 4, (256, 512), (256, 512), 2, 1),
    # blocks of 3 straddle every square of 128: no walk (the diagonal runs
    # as the one square where there is one, whole in the dKV pass), group 1
    (510, 3, (256, 512), (256, 512), 2, 2),
    # blocks of 256: two squares of 256 a q block of 512
    (512, 256, (512, 512), (512, 1024), 2, 1),
    # a length under one block: one tile a copy, no squares
    (100, 4, (256, 512), (None, None), 8, 1)])
def test_streaming_kernel_matches_attention_under_the_diffusion_mask(
        length, block, blocks, bwd, heads, kv_heads):
    """Forward and the three gradients of the kernel against the XLA
    ``attention`` under ``block_diffusion = (L, block)``, grouped heads, at
    blocks that make every kind of tile: a noisy q block's diagonal and its
    clean prefix, a clean k block's two runs of q rows in the dKV pass, a
    ragged length padded apart in each copy, and whatever stands for an
    edge tile (the walked diagonal, the one square, nothing)."""
    from tpudist.parallel.ring_attention import attention
    ks = jax.random.split(jax.random.PRNGKey(length + block), 4)
    d = 16
    q = jax.random.normal(ks[0], (1, 2 * length, heads, d))
    k = jax.random.normal(ks[1], (1, 2 * length, kv_heads, d))
    v = jax.random.normal(ks[2], (1, 2 * length, kv_heads, d))
    w = jax.random.normal(ks[3], (1, 2 * length, heads, d))
    mask = dict(block_diffusion=(length, block))

    def ours(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, block_q=blocks[0], block_k=blocks[1],
            block_q_bwd=bwd[0], block_k_bwd=bwd[1], **mask))

    def plain(q, k, v):
        return jnp.sum(w * attention(q, k, v, **mask))

    got, got_grads = jax.value_and_grad(ours, argnums=(0, 1, 2))(q, k, v)
    want, want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(got) - float(want)) < 1e-4 * max(1.0, abs(float(want)))
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=3e-5)


def test_the_diffusion_mask_is_a_whole_statement():
    q = jnp.ones((1, 16, 2, 8))
    for bad in (dict(causal=True, block_diffusion=(8, 4)),
                dict(block_diffusion=(6, 4))):           # 16 is not 2 x 6
        with pytest.raises(ValueError, match="block_diffusion"):
            flash_attention(q, q, q, **bad)


@pytest.mark.parametrize("stream", ["k", "q"])
@pytest.mark.parametrize("length,block,block_q,block_k", [
    (32, 4, 8, 16), (37, 4, 8, 8), (40, 8, 16, 8), (30, 3, 8, 8),
    (64, 4, 64, 32), (64, 16, 8, 8), (20, 4, 128, 128),
    # rev 7: the walked diagonal and the near square (512 x 1,024 at a
    # quarter), ragged, square tiles, blocks of 3 and of 256, a short row
    (1024, 4, 256, 512), (600, 4, 256, 512), (512, 4, 256, 256),
    (510, 3, 256, 512), (1024, 256, 512, 1024), (100, 4, 256, 512),
    (640, 4, 128, 512)])
def test_the_tiles_that_run_cover_the_diffusion_mask(length, block, block_q,
                                                     block_k, stream):
    """By brute force over positions, whichever side streams: every allowed
    score lies in exactly one tile that runs and in exactly one of the
    rectangles that run of it (a tile's squares lie inside it and no score
    that is skipped is one the mask allows), every tile that runs holds an
    allowed score (nothing above the mask is walked: no tile of clean
    queries by noisy keys, none of a noisy block by another's noisy keys
    beyond its own tiles), lies inside the padded operands, and is interior
    exactly where the mask allows every score of it; ``fill``, ``pairs``,
    ``scores`` and the stated cost are the rectangles that run."""
    from tpudist.ops.pallas.flash_attention import _stream_cost
    from tpudist.parallel.ring_attention import block_diffusion_mask
    band = _diffusion(length, block, block_q, block_k, stream)
    half = band.half
    assert half % band.bq == 0 and half % band.bk == 0 and half >= length
    assert (band.tq_pad, band.tk_pad, band.copies) == (2 * half, 2 * half, 2)
    at = np.concatenate([np.arange(length), half + np.arange(length)])
    padded = np.zeros((2 * half, 2 * half), bool)
    padded[np.ix_(at, at)] = block_diffusion_mask(2 * length, length, block)
    true_row = np.zeros(2 * half, bool)
    true_row[at] = True
    seen = np.zeros(padded.shape, int)
    tiles = band.tiles()
    assert band.masks
    for i in range(band.n):
        assert sum(count for _, count in band.pieces(i)) <= band.steps
    for row0, col0 in tiles:
        assert 0 <= row0 <= 2 * half - band.bq
        assert 0 <= col0 <= 2 * half - band.bk
        tile = (slice(row0, row0 + band.bq), slice(col0, col0 + band.bk))
        seen[tile] += 1
        assert padded[tile].any(), (row0, col0)
        rows_in = padded[tile][true_row[tile[0]]]
        assert bool(band.interior(row0, col0)) == bool(
            rows_in.size and rows_in.all()
            and true_row[tile[1]].all()), (row0, col0)
    assert (seen[padded] == 1).all() and seen.max() <= 1
    # the rectangles that run of those tiles
    run = np.zeros(padded.shape, int)
    rects = band.rects()
    for row0, col0, rows, keys in rects:
        assert rows > 0 and keys > 0
        run[row0:row0 + rows, col0:col0 + keys] += 1
    assert (run[padded] == 1).all() and run.max() <= 1
    assert (seen[run == 1] == 1).all()          # a square lies in its tile
    allowed = int(padded.sum())
    if length % block == 0:
        assert allowed == length * (length + block)
    assert band.pairs() == len(rects) >= len(tiles)
    assert band.scores() == int(run.sum()) <= len(tiles) * band.bq * band.bk
    assert abs(band.fill() - allowed / run.sum()) < 1e-12
    cost = _stream_cost(band, 2, 3, 8, 16, 2, arrays=4, rows=1)
    assert cost.flops == 2 * 2 * 3 * 8 * int(run.sum()) * 16
    assert cost.transcendentals == 3 * 8 * int(run.sum())
    # what stands for a tile: one square where the keys stream and are wider
    # than the rows, else a walk where the diagonal's blocks lie in squares
    # of 128 smaller than the q block, else nothing
    grain = int(np.lcm(block, 128))
    if band.bk % band.bq:
        want = None
    elif stream == "k" and band.bk > band.bq:
        want = (1, band.bq)
    elif band.bq % grain == 0 and grain < band.bq:
        want = (band.bq // grain, grain)
    else:
        want = None
    assert band.squares == want
    if want is None:
        assert len(rects) == len(tiles)
    else:
        cut = [bool(band.part(*tile)[0]) for tile in tiles]
        assert any(cut) and len(rects) == len(tiles) + (want[0] - 1) * sum(cut)
        # squares stand only for a tile that an edge crosses
        assert not any(c and band.interior(*tile)
                       for c, tile in zip(cut, tiles))


def test_blocks_follow_the_diffusion_mask_at_the_cells_shape():
    """Two rows of 8,192 ids as `[x_t ; x_0]`, 32 heads over 4 of 128, blocks
    of 4: the plan the dispatch line reports, the tiles each pass runs
    (never the 2L x 2L square's, nor a causal band's over 2L, which is
    twice the mask) and, since rev 7, the squares that stand for an edge
    tile: a fill of 0.889 forward, 0.928 dQ and 0.877 dKV where whole tiles
    gave 0.80 (forward, dKV) and 0.889 (dQ)."""
    from tpudist.ops.pallas.flash_attention import (_default_blocks,
                                                    program_plan)
    length, block = 8192, 4
    plan = program_plan(2 * length, 32, 128, "bfloat16", kv_heads=4,
                        block_diffusion=(length, block))
    rule = _default_blocks(2 * length, 2 * length, None, 8)
    assert plan == {"schedule": "streaming", "heads_per_program": 8,
                    "block_q": rule.fwd[0], "block_k": rule.fwd[1],
                    "band_fill": 0.8893,
                    "mask": "block_diffusion", "block_length": block}
    allowed = length * (length + block)
    causal_over_2l = 2 * length * (2 * length + 1) // 2
    # forward (512 x 1,024): the sixteen diagonal tiles and sixteen of the 32
    # last tiles of a clean prefix run the 512 keys that face their rows;
    # dQ (512 x 512) and dKV (512 x 1,024): the sixteen diagonal tiles run
    # four squares of 128 each
    for blocks, stream, tiles, whole_fill, squares, cut, run, fill in zip(
            rule, "kkq", (160, 288, 160), (0.8004, 0.8893, 0.8004),
            ((1, 512), (4, 128), (4, 128)), (32, 16, 16),
            (160 * 524288 - 32 * 262144, 288 * 262144 - 16 * 196608,
             160 * 524288 - 16 * 458752), (0.8893, 0.928, 0.8771)):
        band = _diffusion(length, block, *blocks, stream)
        assert len(band.tiles()) == tiles and band.squares == squares
        assert round(allowed / (tiles * band.bq * band.bk), 4) == whole_fill
        assert sum(bool(band.part(*at)[0]) for at in band.tiles()) == cut
        assert band.pairs() == tiles + cut * (squares[0] - 1)
        assert band.scores() == run
        assert round(band.fill(), 4) == fill
        assert allowed <= band.scores() < 0.65 * causal_over_2l
        assert (band.half, band.granule) == (length, 128)
    # forward at 512 x 1,024: 72 tiles of the clean triangle, 72 of the
    # noisy rows' clean prefix, 16 diagonal ones; a tile in five is an edge
    fwd = _diffusion(length, block, *rule.fwd)
    tiles = fwd.tiles()
    assert len(tiles) == 72 + 72 + 16 and fwd.steps == 9
    inside = sum(bool(fwd.interior(*at)) for at in tiles)
    assert inside == len(tiles) - 16 - 2 * 16
    # a causal or windowed call's plan says nothing of a mask of its own
    assert "mask" not in program_plan(length, 32, 128, "bfloat16",
                                      kv_heads=4, causal=True, window=1024)


def test_dispatch_key_and_event_carry_the_diffusion_mask():
    from tpudist.ops import attention_dispatch as ad
    key = ad.shape_key(2, 16384, 32, 128, jnp.bfloat16, True, False,
                       kv_heads=4, block_diffusion=(8192, 4))
    assert key == "b2_t16384_h32_kv4_d128_bfloat16_train_bd4"
    plan = ad.program(16384, 32, 128, "bfloat16", kv_heads=4,
                      block_diffusion=(8192, 4))
    fields = ad.event_fields({
        "kernel": "flash", "mode": "on", "source": "forced", "key": key,
        "schedule": plan["schedule"], "programs": [plan]})
    assert fields["mask"] == ["block_diffusion"]
    assert fields["block_length"] == [4]
    assert fields["heads_per_program"] == [8]
    assert fields["block_q"] == [512] and fields["block_k"] == [1024]
    assert fields["band_fill"] == [plan["band_fill"]]
    from tpudist.telemetry import validate_event
    validate_event({"type": "attention_dispatch", "t": 0.0, "rank": 0,
                    "attempt": 0, **fields})
    # a causal workload's event has no such field
    causal = ad.program(8192, 32, 128, "bfloat16", kv_heads=4, causal=True)
    assert "mask" not in ad.event_fields({
        "kernel": "flash", "mode": "on", "source": "forced", "key": "k",
        "schedule": "streaming", "programs": [causal]})


@pytest.mark.parametrize("window,blocks,fill", [
    (1024, (256, 1280), 0.75), (None, (512, 1024), 0.889)],
    ids=["windowed", "full"])
def test_a_causal_calls_plan_and_kernels_are_what_they_were(window, blocks,
                                                            fill):
    """The decoder's two attention workloads (two sequences of 8,192, 32
    heads over 4 of 128, a window of 1,024 or none) share the three kernel
    bodies with the diffusion mask: their band states no squares for a tile, so
    the plan is rev 5's and each kernel traces the two paths it had, the
    tile without a mask and the tile with one: a product for each of the
    nine a member makes, no slice of a block, and no branch beyond them."""
    from tpudist.ops.pallas.flash_attention import (_Band, _default_blocks,
                                                    program_plan)
    t, h, hkv, d = 8192, 32, 4, 128
    assert program_plan(t, h, d, "bfloat16", kv_heads=hkv, causal=True,
                        window=window) == {
        "schedule": "streaming", "heads_per_program": 8,
        "block_q": blocks[0], "block_k": blocks[1], "band_fill": fill}
    rule = _default_blocks(t, t, window, h // hkv)
    for (bq, bk), stream in zip(rule, "kkq"):
        band = _Band(causal=True, window=window, block_q=bq, block_k=bk,
                     q_len=t, k_len=t, stream=stream)
        assert band.squares is None
        assert band.scores() == band.pairs() * bq * bk

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False).astype(jnp.float32).sum()
    q = jax.ShapeDtypeStruct((2, t, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, t, hkv, d), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k))
    assert text.count("pallas_call") == 3
    # forward 2 products a member, dQ 3, dKV 4; two paths; 8 members
    assert text.count("dot_general") == 2 * 8 * (2 + 3 + 4)
    # a kernel: its first step, the two paths, its last step
    assert text.count("cond[") == 3 * 4
    assert "multiple_of" not in text and "dynamic_slice" not in text
