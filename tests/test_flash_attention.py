"""Pallas flash attention golden tests: the fused kernel (interpreter mode on
CPU — same kernel body that compiles on TPU) must match plain softmax
attention bit-for-nearly-bit, across padded/unpadded lengths, causal masks,
multiple block shapes, and bf16 inputs."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.pallas import flash_attention
from tpudist.parallel.ring_attention import attention


def _qkv(b=2, t=64, h=4, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [64, 128, 197, 256])
def test_flash_matches_plain(t, causal):
    q, k, v = _qkv(b=2, t=t, h=2, d=32)
    got = flash_attention(q, k, v, causal=causal)
    want = attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_small_blocks_multi_kblock():
    # Force several k blocks so the online-softmax carry path is exercised.
    q, k, v = _qkv(b=1, t=128, h=2, d=16)
    got = flash_attention(q, k, v, block_q=32, block_k=32)
    want = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_causal_small_blocks():
    q, k, v = _qkv(b=1, t=96, h=1, d=16, seed=3)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_inputs():
    q, k, v = _qkv(b=1, t=64, h=2, d=32, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    want = attention(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_flash_grad_flows():
    q, k, v = _qkv(b=1, t=32, h=1, d=16)

    def loss(q):
        return flash_attention(q, k, v).sum()

    g = jax.grad(loss)(q)
    assert g.shape == q.shape
    assert bool(jnp.all(jnp.isfinite(g)))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [64, 197])
def test_flash_backward_matches_plain(t, causal):
    """The blockwise Pallas backward (dq/dk/dv from recomputed p) must match
    XLA attention's autodiff, including padded lengths and causal masks."""
    q, k, v = _qkv(b=2, t=t, h=2, d=32, seed=5)
    rng = np.random.default_rng(9)
    g = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal=causal) * g).sum()

    def plain_loss(q, k, v):
        return (attention(q, k, v, causal=causal) * g).sum()

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


def test_flash_backward_small_blocks_cross_lengths():
    # Multi-block accumulation in BOTH kernels + tq != tk causal offset.
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 96, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 96, 2, 16)), jnp.float32)

    def flash_loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               block_q=32, block_k=32).sum()

    def plain_loss(q, k, v):
        return attention(q, k, v, causal=True).sum()

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_vit_attention_flash_vs_xla():
    # The ViT encoder's attention must be numerically identical whichever
    # backend path (fused Pallas kernel vs plain XLA attention) is taken.
    from tpudist.models.vit import MultiHeadAttention

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 197, 64)), jnp.float32)
    key = jax.random.PRNGKey(0)
    mha_xla = MultiHeadAttention(num_heads=4, flash=False)
    variables = mha_xla.init(key, x)
    want = mha_xla.apply(variables, x)
    got = MultiHeadAttention(num_heads=4, flash=True).apply(variables, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [128, 257, 1024])
@pytest.mark.parametrize("d", [32, 64])
def test_flash_backward_parity_matrix(t, d, causal, dtype):
    """Gradient parity for the rebuilt two-pass backward across the ISSUE-5
    acceptance matrix: head_dim ∈ {32, 64} × seq ∈ {128, 257 (ragged),
    1024} × causal on/off × {f32, bf16}, dQ/dK/dV each within atol/rtol ≤
    1e-5 (f32) / 1e-2 (bf16) of XLA attention's autodiff — in interpreter
    mode on CPU, so the matrix rides tier-1. t=1024 uses 256-blocks (fewer
    interpreter grid steps AND a second block-size point; 257 exercises the
    ragged key-padding mask)."""
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    tol = 1e-5 if dtype == "float32" else 1e-2
    blocks = 256 if t >= 1024 else 128
    rng = np.random.default_rng(t + d + causal)
    shape = (1, t, 1, d)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dt) for _ in range(3))
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=blocks,
                                block_k=blocks).astype(jnp.float32)
                * g).sum()

    def plain_loss(q, k, v):
        return (attention(q, k, v, causal=causal).astype(jnp.float32)
                * g).sum()

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(
            a, b, rtol=tol, atol=tol * max(1e-6, float(np.abs(b).max())),
            err_msg=f"d{name} t={t} d={d} causal={causal} {dtype}")


def test_flash_backward_blocks_decoupled_from_forward():
    """block_q_bwd/block_k_bwd tune the backward independently of the
    forward's blocks (the dKV pass wants its resident tile on KV): different
    backward tilings must be grad-identical, including when the backward's
    q padding differs from the forward's (lse re-pad path)."""
    q, k, v = _qkv(b=1, t=100, h=2, d=32, seed=17)

    def loss(bq_bwd, bk_bwd):
        def f(q, k, v):
            return flash_attention(q, k, v, block_q=64, block_k=64,
                                   block_q_bwd=bq_bwd,
                                   block_k_bwd=bk_bwd).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    base = loss(None, None)                 # bwd inherits fwd 64/64
    other = loss(32, 96)                    # ragged, different q padding
    for name, a, b in zip("qkv", other, base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5, err_msg=f"d{name}")


def test_flash_causal_cross_attention_lengths():
    # t_q != t_k: the causal mask must use the same tril offset (t_k - t_q)
    # as the XLA attention — the last query row sees every key.
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 16)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -- the whole-sequence schedule and the fused QKV entry (PR 28) --------------

def _fused(b=2, t=197, h=2, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((b, t, h, 3, d)), dtype)


@pytest.mark.parametrize("t,heads,d,want", [
    (197, 12, 64, "whole_seq"),     # ViT-B/16
    (256, 12, 64, "whole_seq"),
    (50, 2, 64, "whole_seq"),
    (197, 6, 64, "whole_seq"),      # ViT-B/16 under tp=2
    (2048, 12, 64, "streaming"),    # scores outgrow the VMEM budget
    (197, 3, 64, "streaming"),      # odd head count: no lane-aligned group
    (197, 2, 32, "streaming"),      # 2 * 96 columns fill no 128-lane tile
])
def test_schedule_follows_the_shape(t, heads, d, want):
    from tpudist.ops.pallas.flash_attention import schedule_for
    assert schedule_for(t, heads, d, jnp.bfloat16) == want


def test_head_group_is_the_largest_that_fits():
    # (the package re-exports the function under the module's name)
    fa = importlib.import_module("tpudist.ops.pallas.flash_attention")
    assert fa._head_group(197, 12, 64, 2) == 12
    assert fa._head_group(197, 6, 64, 2) == 6
    assert fa._head_group(197, 3, 64, 2) is None
    assert fa._head_group(197, 4, 32, 2) == 4
    # a longer sequence leaves room for fewer heads, then for none
    assert fa._head_group(640, 12, 64, 2) == 2
    assert fa._head_group(2048, 12, 64, 2) is None
    for t, h, d in ((197, 12, 64), (640, 12, 64), (512, 16, 64)):
        g = fa._head_group(t, h, d, 2)
        assert fa._whole_seq_vmem_bytes(t, d, g, 2) <= fa._VMEM_BUDGET


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [50, 197, 256])
def test_whole_seq_matches_plain(t, causal, dtype):
    """The whole-sequence schedule (head_dim 64, two heads: one
    lane-aligned group) against ``attention``, forward and backward, under
    the streaming kernel's tolerances."""
    from tpudist.ops.pallas.flash_attention import (flash_attention_qkv,
                                                    schedule_for)
    from tpudist.parallel.ring_attention import split_qkv
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    ftol, gtol = (2e-5, 1e-5) if dtype == "float32" else (2e-2, 1e-2)
    qkv = _fused(t=t, seed=t + causal, dtype=dt)
    assert schedule_for(t, 2, 64, dt) == "whole_seq"
    g = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, t, 2, 64)), jnp.float32)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731

    def plain(x, causal):
        return attention(*split_qkv(x), causal=causal)

    got = flash_attention_qkv(qkv, causal=causal)
    want = plain(qkv, causal)
    assert got.dtype == dt
    np.testing.assert_allclose(f32(got), f32(want), rtol=ftol, atol=ftol)

    def loss(fn):
        return lambda x: (fn(x, causal=causal).astype(jnp.float32) * g).sum()

    got = split_qkv(jax.grad(loss(flash_attention_qkv))(qkv))
    want = split_qkv(jax.grad(loss(plain))(qkv))
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            f32(a), f32(b), rtol=gtol,
            atol=gtol * max(1e-6, float(np.abs(f32(b)).max())),
            err_msg=f"d{name} t={t} causal={causal} {dtype}")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads", [2, 4])
def test_fused_entry_matches_split_entry(heads, causal):
    """``flash_attention_qkv`` on [B, T, H, 3, D] against the split entry
    on its slices, to float32 rounding; its gradient arrives in the
    projection's own layout and matches the streaming kernels' (the split
    entry)."""
    from tpudist.ops.pallas import flash_attention_qkv
    from tpudist.parallel.ring_attention import split_qkv
    qkv = _fused(t=197, h=heads, seed=heads)
    g = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 197, heads, 64)), jnp.float32)

    def fused(x):
        return (flash_attention_qkv(x, causal=causal) * g).sum()

    def split(x):
        return (flash_attention(*split_qkv(x), causal=causal) * g).sum()

    np.testing.assert_allclose(
        np.asarray(flash_attention_qkv(qkv, causal=causal)),
        np.asarray(flash_attention(*split_qkv(qkv), causal=causal)),
        rtol=2e-5, atol=2e-5)
    got, want = jax.grad(fused)(qkv), jax.grad(split)(qkv)
    assert got.shape == qkv.shape == (2, 197, heads, 3, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_fused_entry_falls_back_to_split_where_no_group_fits(monkeypatch):
    """An odd head count has no lane-aligned group: the fused entry goes
    through slices and the split entry (the streaming kernels), same
    numbers, gradient still in the projection's layout."""
    from tpudist.ops.pallas import flash_attention_qkv
    from tpudist.parallel.ring_attention import split_qkv
    fa = importlib.import_module("tpudist.ops.pallas.flash_attention")
    qkv = _fused(b=1, t=64, h=3, d=64, seed=11)
    assert fa.schedule_for(64, 3, 64, qkv.dtype) == "streaming"
    monkeypatch.setattr(fa, "_qkv_vjp", lambda *a, **k: pytest.fail(
        "the whole-sequence kernels ran on a shape they cannot tile"))
    got = flash_attention_qkv(qkv)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(attention(*split_qkv(qkv))),
                               rtol=2e-5, atol=2e-5)
    grad = jax.grad(lambda x: flash_attention_qkv(x).sum())(qkv)
    want = jax.grad(lambda x: attention(*split_qkv(x)).sum())(qkv)
    assert grad.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_whole_seq_says_what_it_costs():
    """``mfu_pct`` divides the executable's counted FLOPs by the window: a
    Pallas call counts what its ``cost_estimate`` says. The algorithm's
    products at the true length, forward 4 T^2 d and backward 8 T^2 d a
    head — no padding, no recompute."""
    from tpudist.ops.pallas import flash_attention_qkv
    b, t, h, d = 2, 197, 2, 64
    qkv = _fused(b=b, t=t, h=h, d=d, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: flash_attention_qkv(
        x).astype(jnp.float32).sum()))(qkv)
    costs = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                costs.append(eqn.params["cost_estimate"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert sorted(c.flops for c in costs) == [
        4 * b * h * t * t * d, 8 * b * h * t * t * d]
    assert all(c.transcendentals == b * h * t * t for c in costs)
    assert all(c.bytes_accessed > 0 for c in costs)


@pytest.mark.parametrize("causal", [False, True])
def test_vit_block_flash_vs_xla_outputs_and_param_grads(causal):
    """``MultiHeadAttention`` at ViT-B/16's token count with ``flash=True``
    (the fused entry on the whole-sequence schedule) against ``flash=False``:
    outputs and every parameter gradient."""
    from tpudist.models.vit import MultiHeadAttention
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 197, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 197, 128)), jnp.float32)
    xla = MultiHeadAttention(num_heads=2, flash=False, causal=causal)
    variables = xla.init(jax.random.PRNGKey(0), x)
    flash = MultiHeadAttention(num_heads=2, flash=True, causal=causal)

    def loss(mod):
        return lambda v: (mod.apply(v, x) * w).sum()

    np.testing.assert_allclose(np.asarray(flash.apply(variables, x)),
                               np.asarray(xla.apply(variables, x)),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(loss(flash))(variables)
    want = jax.grad(loss(xla))(variables)
    flat_g = jax.tree_util.tree_leaves_with_path(got)
    flat_w = jax.tree_util.tree_leaves(want)
    assert len(flat_g) == 4                    # in_proj / out_proj, w + b
    for (path, a), b_ in zip(flat_g, flat_w):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=2e-4,
            atol=2e-4 * float(np.abs(np.asarray(b_)).max()),
            err_msg=jax.tree_util.keystr(path))


# -- a window, and fewer key-value heads than query heads --------------------

@pytest.mark.parametrize("t", [32, 37, 100])
@pytest.mark.parametrize("window,kv_heads", [(8, 2), (40, 1), (None, 2)])
def test_streaming_kernel_matches_windowed_grouped_attention(t, window,
                                                             kv_heads):
    """Interpret mode, forward and gradients, blocks of 16 x 32: lengths
    that are and are not block multiples, a window smaller and larger than
    a block, 4 query heads over 1 and 2 key-value heads."""
    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    q = jax.random.normal(ks[0], (2, t, 4, 16))
    k = jax.random.normal(ks[1], (2, t, kv_heads, 16))
    v = jax.random.normal(ks[2], (2, t, kv_heads, 16))
    g = jax.random.normal(ks[3], (2, t, 4, 16))

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * g), argnums=(0, 1, 2))(
                q, k, v)

    got, got_grads = both(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=16, block_k=32))
    want, want_grads = both(lambda q, k, v: attention(
        q, k, v, causal=True, window=window))
    assert abs(float(got) - float(want)) < 1e-3
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


# -- a program holds a key-value head's whole group of query heads (PR 33) ---

@pytest.mark.parametrize("d", [128, 16], ids=["d128", "d16"])
@pytest.mark.parametrize("t", [48, 37], ids=["tiles", "ragged"])
@pytest.mark.parametrize("window", [8, 16, 24, None])
@pytest.mark.parametrize("group", [1, 2, 8])
def test_grouped_program_matches_attention(group, window, t, d):
    """Interpret mode, forward and dQ / dK / dV against ``attention``, blocks
    of 16 x 16: one, two and eight query heads a key-value head, a window
    shorter than, equal to and longer than a block and none, a length that
    is and is not a block multiple, a head size that is whole lane tiles
    and one that is not."""
    kv_heads = 2 if group < 8 else 1
    ks = jax.random.split(jax.random.PRNGKey(group * t + d), 4)
    q = jax.random.normal(ks[0], (1, t, group * kv_heads, d))
    k = jax.random.normal(ks[1], (1, t, kv_heads, d))
    v = jax.random.normal(ks[2], (1, t, kv_heads, d))
    g = jax.random.normal(ks[3], q.shape)

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * g), argnums=(0, 1, 2))(
                q, k, v)

    got, got_grads = both(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=16, block_k=16))
    want, want_grads = both(lambda q, k, v: attention(
        q, k, v, causal=True, window=window))
    assert abs(float(got) - float(want)) < 2e-3
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-5)


@pytest.mark.parametrize("kv_heads,group", [(1, 16), (2, 16), (1, 24),
                                            (1, 12)])
def test_a_group_of_sixteen_is_split_over_programs_of_eight(kv_heads, group):
    """32 query heads over 2 key-value heads: a group of sixteen (or any
    multiple of eight) runs as programs of eight over repeated k and v, and
    is ``attention``'s result and gradients, dK and dV summed over the
    copies; a group that eight does not divide stays one program."""
    fa = importlib.import_module("tpudist.ops.pallas.flash_attention")
    assert [fa._programs_of(g) for g in (1, 4, 8, 12, 16, 24, 32)] == [
        1, 1, 1, 1, 2, 3, 4]
    plan = fa.program_plan(8192, 32, 128, jnp.bfloat16, kv_heads=2,
                           causal=True)
    assert (plan["heads_per_program"], plan["block_q"], plan["block_k"]) == (
        8, 512, 1024)
    assert plan == fa.program_plan(8192, 32, 128, jnp.bfloat16, kv_heads=4,
                                   causal=True)
    t, d = 37, 16
    ks = jax.random.split(jax.random.PRNGKey(group + kv_heads), 4)
    q = jax.random.normal(ks[0], (1, t, group * kv_heads, d))
    k = jax.random.normal(ks[1], (1, t, kv_heads, d))
    v = jax.random.normal(ks[2], (1, t, kv_heads, d))
    g = jax.random.normal(ks[3], q.shape)

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * g), argnums=(0, 1, 2))(
                q, k, v)

    got, got_grads = both(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16))
    want, want_grads = both(lambda q, k, v: attention(q, k, v, causal=True))
    assert abs(float(got) - float(want)) < 2e-3
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("kv_heads", [1, 3])
def test_grouped_program_full_and_cross_lengths(kv_heads):
    """No mask at all (non-causal, exact tiling), a ragged key length
    (the last k block alone builds a mask) and more keys than queries under
    a causal mask, six query heads over one and three key-value heads."""
    ks = jax.random.split(jax.random.PRNGKey(kv_heads), 3)
    for t, tk, causal in ((16, 32, False), (16, 25, False), (16, 40, True)):
        q = jax.random.normal(ks[0], (1, t, 6, 128))
        k = jax.random.normal(ks[1], (1, tk, kv_heads, 128))
        v = jax.random.normal(ks[2], (1, tk, kv_heads, 128))

        def loss(fn):
            return jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)

        got = loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=8, block_k=16))
        want = loss(lambda q, k, v: attention(q, k, v, causal=causal))
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("d", [128, 16], ids=["d128", "d16"])
@pytest.mark.parametrize("t", [64, 61], ids=["tiles", "ragged"])
@pytest.mark.parametrize("window", [8, 20, 33])
@pytest.mark.parametrize("blocks", [(8, 24), (16, 40), (24, 8)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_steps_slide_with_the_window(blocks, window, t, d):
    """Blocks that are no multiple of one another, so that the streamed
    side's steps start where the band does (rounded down to a granule of 8)
    and not at a block multiple: forward, dQ (keys stream) and dK / dV
    (queries stream) against ``attention``, two query heads a key-value
    head."""
    from tpudist.ops.pallas.flash_attention import _Band
    bands = [_Band(causal=True, window=window, block_q=blocks[0],
                   block_k=blocks[1], q_len=t, k_len=t, stream=side)
             for side in "kq"]
    assert min(band.granule for band in bands) == 8
    ks = jax.random.split(jax.random.PRNGKey(window + t + d), 4)
    q = jax.random.normal(ks[0], (1, t, 4, d))
    k = jax.random.normal(ks[1], (1, t, 2, d))
    v = jax.random.normal(ks[2], (1, t, 2, d))
    g = jax.random.normal(ks[3], q.shape)

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * g), argnums=(0, 1, 2))(
                q, k, v)

    got, got_grads = both(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=blocks[0],
        block_k=blocks[1]))
    want, want_grads = both(lambda q, k, v: attention(
        q, k, v, causal=True, window=window))
    assert abs(float(got) - float(want)) < 2e-3
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=3e-5)


_DECODER = dict(seq=8192, heads=32, head_dim=128, dtype="bfloat16")


@pytest.mark.parametrize("window,least", [(1024, 0.75), (None, 0.88)],
                         ids=["windowed", "full"])
def test_blocks_follow_the_band_at_the_decoder_shape(window, least):
    """The block rule and ``band_fill`` as static numbers: eight query heads
    a program, and the scores the forward programs run are at most 1.34 x
    (windowed; 2.0 x with one head a program at blocks of 1,024) and 1.14 x
    (full) the scores the mask allows."""
    from tpudist.ops.pallas.flash_attention import (_Band, _default_blocks,
                                                    program_plan)
    plan = program_plan(**_DECODER, kv_heads=4, causal=True, window=window)
    assert plan["schedule"] == "streaming"
    assert plan["heads_per_program"] == 8
    rule = _default_blocks(8192, 8192, window, 8)
    assert (plan["block_q"], plan["block_k"]) == rule.fwd
    assert least <= plan["band_fill"] <= 1.0
    # the same count by hand: rows x the keys each may see
    allowed = (8192 * 8193 // 2 if window is None
               else window * 8192 - window * (window - 1) // 2)
    for blocks, stream in zip(rule, "kkq"):
        band = _Band(causal=True, window=window, block_q=blocks[0],
                     block_k=blocks[1], q_len=8192, k_len=8192,
                     stream=stream)
        assert abs(band.fill() - allowed / (
            band.pairs() * band.bq * band.bk)) < 1e-12
        # every pass follows the band more closely than the parent's
        # blocks of 1,024 did (0.5 windowed, 0.889 full)
        assert band.fill() > (0.5 if window else 0.88)
        # what the chip's memory tiling asks of an element offset
        assert band.granule == 128
        assert (band.tq_pad, band.tk_pad) == (8192, 8192)
    # one head a program at blocks of 1,024: what the parent ran
    one = program_plan(**_DECODER, causal=True, window=window)
    assert (one["block_q"], one["block_k"]) == (1024, 1024)
    assert one["band_fill"] == (0.5 if window else 0.889)


@pytest.mark.parametrize("t,tk,window", [
    (197, 197, None), (1024, 1024, None), (1025, 640, None),
    (2048, 2048, None), (8192, 8192, 1024), (8192, 8192, None),
    (300, 8192, 64)])
def test_one_head_a_program_keeps_the_parents_blocks(t, tk, window):
    """``G = 1``: 128 up to 1,024 positions and 1,024 beyond, each side by
    its own length, whatever the window, in all three kernels: what was
    measured."""
    from tpudist.ops.pallas.flash_attention import _default_blocks
    want = (128 if t <= 1024 else 1024, 128 if tk <= 1024 else 1024)
    assert set(_default_blocks(t, tk, window, 1)) == {want}


def _band(t, tk, causal, window, block_q, block_k, stream="k"):
    from tpudist.ops.pallas.flash_attention import _Band
    return _Band(causal=causal, window=window, block_q=block_q,
                 block_k=block_k, q_len=t, k_len=tk, stream=stream)


def test_a_pair_inside_the_band_builds_no_mask():
    """``_Band.interior``: at blocks of 1,024 a full layer of 8,192 runs 36
    tiles and 28 of them lie wholly under the diagonal; under a window
    the far edge is an edge too; a ragged key length makes one of the last k
    block; a call that needs no mask says so statically."""
    def count(band):
        tiles = band.tiles()
        assert len(tiles) == band.pairs()
        return len(tiles), sum(bool(band.interior(*at)) for at in tiles)

    full = _band(8192, 8192, True, None, 1024, 1024)
    assert count(full) == (36, 28) and full.masks
    assert count(_band(8192, 8192, True, None, 1024, 1024, "q")) == (36, 28)
    banded = _band(8192, 8192, True, 1024, 256, 256)
    # five k blocks a q block: the diagonal's and the window's far one are
    # edges, three between them are not
    assert banded.steps == 5
    assert count(banded) == (banded.pairs(), 3 * (32 - 4) + 3 + 2 + 1)
    ragged = _band(64, 41, False, None, 16, 16)
    assert ragged.masks and count(ragged) == (12, 8)
    square = _band(64, 48, False, None, 16, 16)
    assert not square.masks and count(square) == (12, 12)


@pytest.mark.parametrize("stream", ["k", "q"])
@pytest.mark.parametrize("t,tk,window,block_q,block_k", [
    (64, 64, 24, 16, 8), (64, 64, 24, 8, 24), (61, 61, 20, 24, 8),
    (64, 64, None, 16, 8), (40, 64, 24, 16, 16), (64, 40, 10, 8, 24),
    (64, 64, 7, 16, 40)])
def test_the_tiles_that_run_cover_the_band(t, tk, window, block_q, block_k,
                                           stream):
    """By brute force over positions, whichever side streams and wherever
    its steps start: every allowed score lies in exactly one tile that
    runs, every tile that runs lies inside the padded operands at a
    multiple of the granule, and a tile is interior exactly where the mask
    allows every score of it."""
    band = _band(t, tk, True, window, block_q, block_k, stream)
    rows, cols = np.arange(t)[:, None], np.arange(tk)[None, :]
    ok = cols <= rows + (tk - t)
    if window is not None:
        ok &= rows + (tk - t) - cols < window
    seen = np.zeros((band.tq_pad, band.tk_pad), int)
    padded = np.zeros((band.tq_pad, band.tk_pad), bool)
    padded[:t, :tk] = ok
    assert band.steps >= 1
    for i in range(band.n):
        start, lo, hi = band.span(i)
        assert hi - lo + 1 <= band.steps
    for row0, col0 in band.tiles():
        assert 0 <= row0 <= band.tq_pad - band.bq
        assert 0 <= col0 <= band.tk_pad - band.bk
        assert (col0 if stream == "k" else row0) % band.granule == 0
        tile = (slice(row0, row0 + band.bq), slice(col0, col0 + band.bk))
        seen[tile] += 1
        rows_in = padded[tile][:max(0, min(band.bq, t - row0))]
        assert bool(band.interior(row0, col0)) == bool(
            rows_in.size and rows_in.all()
            and col0 + band.bk <= tk), (row0, col0)
    assert (seen[padded] == 1).all() and seen.max() <= 1

