"""Latent attention (models/decoder.py::LatentAttention; docs/ATTENTION.md,
"Latent attention"): two low-rank paths with a norm inside each, a part of a
head rotated by neighbouring pairs, ONE rotated key head for all the query
heads, keys of 16 + 8 columns against values of 16 at the toy widths
(`joyai_tiny`'s: hidden 64, 4 heads, ranks 48 and 32).

CPU, seeded random weights. The plain reference is the benchmark's
(`refs/joyai_flash_ep16.py::mla`), imported by path: it imports nothing of
the program. The kernels (`ops/pallas/mla_attention.py`) run interpreted.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.decoder import LatentAttention, joyai_tiny
from tpudist.ops.pallas import flash_attention_latent
from tpudist.parallel.ring_attention import attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_joyai_for_mla_tests", os.path.join(
            ROOT, "benchmarks", "chip", "refs", "joyai_flash_ep16.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
HIDDEN = 64
FIELDS = dict(joyai_tiny()._attention_of("full_attention", None))
SIZES = dict(heads=FIELDS["num_heads"], q_rank=FIELDS["q_rank"],
             kv_rank=FIELDS["kv_rank"], nope=FIELDS["nope_dim"],
             rope=FIELDS["rope_dim"], v=FIELDS["v_dim"], eps=1e-6,
             theta=FIELDS["rope_parameters"]["rope_theta"], rows=16)


def rel_gap(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def module_and_weights(flash, seed=0):
    module = LatentAttention(**dict(FIELDS, flash=flash), dtype=jnp.float32)
    params = module.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, 16, HIDDEN)))["params"]
    # norms that are not 1, so that a norm left out or misplaced shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4))
    for name in ("q_a_norm", "kv_a_norm"):
        scale = params[name]["scale"]
        params[name]["scale"] = scale + 0.3 * jax.random.normal(
            next(keys), scale.shape)
    # weights of a size at which the scores differ between positions
    params = jax.tree_util.tree_map(
        lambda x: x * (8.0 if x.ndim == 2 else 1.0), params)
    return module, params


def both(module, params, u, weight, wrong=None):
    """((program's sum, its gradients), (the reference's)) of a weighted sum
    of the layer's output, with respect to the weights and the input."""
    def ours(p, x):
        return jnp.sum(module.apply({"params": p}, x) * weight)

    def theirs(p, x):
        return jnp.sum(REF.mla(x, p, SIZES, wrong=wrong) * weight)
    with jax.default_matmul_precision("highest"):
        return (jax.value_and_grad(ours, argnums=(0, 1))(params, u),
                jax.value_and_grad(theirs, argnums=(0, 1))(params, u))


def gaps(got, want):
    (a, ga), (b, gb) = got, want
    out = {"value": abs(float(a) - float(b)) / abs(float(b))}
    for (path, g), (_, w) in zip(jax.tree_util.tree_leaves_with_path(ga),
                                 jax.tree_util.tree_leaves_with_path(gb)):
        out[jax.tree_util.keystr(path)] = rel_gap(g, w)
    return out


def inputs(t, seed=2):
    ku, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ku, (2, t, HIDDEN), jnp.float32),
            jax.random.normal(kw, (2, t, HIDDEN), jnp.float32))


# 160 positions are padded to 256: two blocks of 128 a pass, a ragged row
@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("t", [32, 37, 160])
def test_the_module_is_the_references_mla(t, flash):
    module, params = module_and_weights(flash)
    u, weight = inputs(t)
    worst = gaps(*both(module, params, u, weight))
    assert len(worst) == 1 + 7 + 1         # the sum, seven leaves, the input
    assert max(worst.values()) < 2e-5, worst


@pytest.mark.parametrize("wrong", ["scale_nope", "rotate_halves",
                                   "key_a_head", "no_latent_norm"])
def test_a_wrong_layer_fails_the_comparison(wrong):
    """A temperature of `nope^-1/2`, halves rotated for pairs, a rotated key
    a head, a latent norm left out: each is far outside what the sound
    comparison holds (2e-5), in the output and in a gradient."""
    module, params = module_and_weights(flash=False)
    u, weight = inputs(37)
    worst = gaps(*both(module, params, u, weight, wrong=wrong))
    assert worst["value"] > 1e-3, worst
    assert max(v for k, v in worst.items() if k != "value") > 1e-2, worst


def test_the_rotated_columns_order_is_free_and_shared():
    """The program lays the rotated columns [evens | odds], the reference
    leaves each pair where it lay: the scores are the same, because q and k
    share the order (`rope.apply_pairs`)."""
    from tpudist.ops import rope
    t, d = 24, 8
    cos, sin = rope.tables({"rope_theta": 32000000}, d, t)
    kq, kk = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(kq, (2, t, 3, d))
    k = jax.random.normal(kk, (2, t, 1, d))
    z = dict(rope=d, theta=32000000.0)
    ours = jnp.einsum("bqhd,bkhd->bhqk", rope.apply_pairs(q, cos, sin),
                      jnp.broadcast_to(rope.apply_pairs(k, cos, sin),
                                       q.shape))
    theirs = jnp.einsum("bqhd,bkhd->bhqk", REF._rotate(q, z),
                        jnp.broadcast_to(REF._rotate(k, z), q.shape))
    np.testing.assert_allclose(ours, theirs, atol=1e-5)
    # and the rotation is by pairs: rotate-half of the same columns differs
    halves = jnp.einsum("bqhd,bkhd->bhqk", rope.apply(q, cos, sin),
                        jnp.broadcast_to(rope.apply(k, cos, sin), q.shape))
    assert float(jnp.max(jnp.abs(halves - theirs))) > 0.1


# --- the kernel entry alone --------------------------------------------------

def xla_expression(q_nope, q_rope, k_nope, k_rope_by_head, v):
    """The same attention over keys laid whole, a rotated key a head."""
    return attention(jnp.concatenate([q_nope, q_rope], -1),
                     jnp.concatenate([k_nope, k_rope_by_head], -1), v,
                     causal=True)


def operands(t, dtype=jnp.float32, heads=3, dn=16, dr=8, dv=8):
    keys = jax.random.split(jax.random.PRNGKey(t), 6)
    shapes = [(2, t, heads, dn), (2, t, heads, dr), (2, t, heads, dn),
              (2, t, dr), (2, t, heads, dv)]
    return [jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes)], \
        jax.random.normal(keys[5], (2, t, heads, dv), jnp.float32)


@pytest.mark.parametrize("t,block_q,block_k", [
    (40, None, None), (256, 128, 128), (300, 128, 128), (300, 128, 256),
    (300, 256, 128)])
def test_the_entry_is_the_xla_expression(t, block_q, block_k):
    """Values (8) narrower than keys (16 + 8); one block, whole blocks, a
    ragged row, blocks that differ a side. Output and all five gradients to
    float32 rounding; the shared rotated key's gradient is the sum over the
    heads of the gradients a key a head would get."""
    (qn, qr, kn, kr, v), weight = operands(t)
    by_head = jnp.broadcast_to(kr[:, :, None], qr.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(loss(xla_expression), argnums=(
            0, 1, 2, 3, 4))(qn, qr, kn, by_head, v)
        got, gg = jax.value_and_grad(loss(lambda *a: flash_attention_latent(
            *a, block_q=block_q, block_k=block_k)), argnums=(
                0, 1, 2, 3, 4))(qn, qr, kn, kr, v)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want)) + 1e-5
    gw = list(gw)
    assert gw[3].shape == qr.shape and gg[3].shape == kr.shape
    gw[3] = gw[3].sum(axis=2)
    for name, g, w in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"),
                          gg, gw):
        assert rel_gap(g, w) < 1e-5, name


def test_the_entry_in_bfloat16_is_near_the_float32_expression():
    (qn, qr, kn, kr, v), _ = operands(192, jnp.bfloat16)
    got = flash_attention_latent(qn, qr, kn, kr, v)
    assert got.dtype == jnp.bfloat16 and got.shape == v.shape
    want = xla_expression(*(x.astype(jnp.float32) for x in (
        qn, qr, kn, jnp.broadcast_to(kr[:, :, None], qr.shape), v)))
    assert rel_gap(got.astype(jnp.float32), want) < 2e-2


def test_the_entry_refuses_what_it_does_not_compute():
    (qn, qr, kn, kr, v), _ = operands(32)
    with pytest.raises(ValueError, match="one head"):
        flash_attention_latent(qn, qr, kn, jnp.broadcast_to(
            kr[:, :, None], qr.shape), v)
    with pytest.raises(ValueError, match="lane tiles"):
        flash_attention_latent(qn, qr, kn, kr, v, block_q=64)


def test_the_calls_state_the_true_widths():
    """`pl.CostEstimate` of the three calls: the products over the keys'
    true 192 columns and the values' 128 on the tiles that run, never a
    padded 256."""
    b, t, h = 1, 2048, 2
    shapes = [(b, t, h, 128), (b, t, h, 64), (b, t, h, 128), (b, t, 64),
              (b, t, h, 128)]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: flash_attention_latent(
        *a).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
    stated = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                stated[eqn.params["jaxpr"].debug_info.func_name] = eqn.params[
                    "cost_estimate"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert sorted(stated) == ["_dkv_kernel", "_dq_kernel", "_fwd_kernel"]
    tiles = 3 * 1024 * 1024                  # blocks of 1,024: 3 of 4 run
    for name, cost in stated.items():
        assert cost.flops == 2 * b * h * tiles * (192 + 128), name
        assert cost.transcendentals == b * h * tiles, name
    # q, k_nope, v, o of every head and the rotated key once, bfloat16; the
    # float32 logsumexp
    assert stated["_fwd_kernel"].bytes_accessed == (
        b * t * (h * (192 + 128 + 128 + 128) + 64) * 2 + 4 * b * h * t)


# --- the rotation as one pass, and the kernels' addressed entry --------------
# (ops/pallas/latent_rope.py; mla_attention.py::flash_attention_latent_laid)

from tpudist.ops import rope  # noqa: E402
from tpudist.ops.pallas import flash_attention_latent_laid  # noqa: E402
from tpudist.ops.pallas.latent_rope import (  # noqa: E402
    latent_plan, latent_rope, pair_tables)

DN, DR, DV, RANK = 128, 64, 128, 128
THETA = {"rope_theta": 32000000.0}


def _raw(t, heads, dtype=jnp.float32, seed=0):
    """q as q_b_proj writes it, kv_a_proj's result (the rotated key its last
    columns), and a weight an output of the pass."""
    ks = jax.random.split(jax.random.PRNGKey(seed + t + heads), 5)
    shapes = [(2, t, heads * (DN + DR)), (2, t, RANK + DR),
              (2, heads, t, DN), (2, heads, t, DR), (2, t, DR)]
    return [jax.random.normal(k, s).astype(dtype if i < 2 else jnp.float32)
            for i, (k, s) in enumerate(zip(ks, shapes))]


def _where_they_lay(x):
    """``rope.apply_pairs``' ``[evens | odds]`` columns back where the pairs
    lay: the permutation the two forms differ by."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], axis=-1).reshape(x.shape)


def _by_jax_numpy(t, heads):
    """The pass's three results by ``rope.apply_pairs`` and XLA's moves."""
    cos, sin = rope.tables(THETA, DR, t)

    def f(q, kva):
        q = q.reshape(2, t, heads, DN + DR)
        turned = _where_they_lay(rope.apply_pairs(q[..., DN:], cos, sin))
        k_r = _where_they_lay(rope.apply_pairs(kva[:, :, None, RANK:], cos,
                                               sin))[:, :, 0]
        return (jnp.moveaxis(q[..., :DN], 1, 2), jnp.moveaxis(turned, 1, 2),
                k_r)
    return f


def _by_the_pass(t, heads):
    cos, sin = rope.tables(THETA, DR, t)
    return lambda q, kva: latent_rope(q, kva, cos, sin, heads=heads,
                                      kv_rank=RANK)


def _out_and_grads(f, q, kva, weights):
    def loss(q, kva):
        out = f(q, kva)
        return sum(jnp.sum(o.astype(jnp.float32) * w)
                   for o, w in zip(out, weights)), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                         has_aux=True)(q, kva)
    return list(out), list(grads)


@pytest.mark.parametrize("heads", [2, 4])
@pytest.mark.parametrize("t", [32, 160, 1024])
def test_the_pass_is_the_jax_numpy_form_in_float32(t, heads):
    """q_nope moved, q_rope and k_r rotated by neighbouring pairs and left
    where they lay: `rope.apply_pairs`' values up to the column permutation
    both sides share, and both cotangents (q whole; kv_a_proj's, zero but
    for the rotated key's columns) to float32 rounding."""
    q, kva, *weights = _raw(t, heads)
    want, want_grads = _out_and_grads(_by_jax_numpy(t, heads), q, kva,
                                      weights)
    got, grads = _out_and_grads(_by_the_pass(t, heads), q, kva, weights)
    assert [x.shape for x in got] == [(2, heads, t, DN), (2, heads, t, DR),
                                      (2, t, DR)]
    np.testing.assert_array_equal(got[0], want[0])
    for a, b in zip(got[1:] + grads, want[1:] + want_grads):
        assert a.shape == b.shape and rel_gap(a, b) < 1e-6
    assert not np.any(np.asarray(grads[1][..., :RANK]))
    # and the order: a score over the pass's columns is one over
    # `apply_pairs`' (the freedom the test above states)
    cos, sin = rope.tables(THETA, DR, t)
    pairs = rope.apply_pairs(q.reshape(2, t, heads, -1)[..., DN:], cos, sin)
    key = rope.apply_pairs(kva[:, :, None, RANK:], cos, sin)[:, :, 0]
    np.testing.assert_allclose(
        jnp.einsum("bhqd,bkd->bhqk", got[1], got[2]),
        jnp.einsum("bqhd,bkd->bhqk", pairs, key), atol=2e-4)


def test_the_pairs_tables_turn_neighbours():
    """`pair_tables`: each frequency on both lanes of its pair, the sine
    signed `[-, +]`; with them `x cos2 + swap(x) sin2` is the rotation of
    `(x_2i, x_2i+1)` by `pos * inv_freq_i`."""
    cos, sin = rope.tables(THETA, 8, 5)
    cos2, sin2 = pair_tables(cos, sin)
    np.testing.assert_array_equal(cos2[:, 0::2], cos[:, :4])
    np.testing.assert_array_equal(cos2[:, 1::2], cos[:, :4])
    np.testing.assert_array_equal(sin2[:, 0::2], -sin[:, :4])
    np.testing.assert_array_equal(sin2[:, 1::2], sin[:, :4])
    x = np.random.default_rng(0).normal(size=(5, 8)).astype(np.float32)
    swapped = x.reshape(5, 4, 2)[..., ::-1].reshape(5, 8)
    got = x * cos2 + swapped * sin2
    angle = np.arctan2(sin[:, :4], cos[:, :4])
    turned = (x[:, 0::2] + 1j * x[:, 1::2]) * np.exp(1j * angle)
    np.testing.assert_allclose(got[:, 0::2], turned.real, atol=1e-6)
    np.testing.assert_allclose(got[:, 1::2], turned.imag, atol=1e-6)


def test_the_pass_rounds_once():
    """bfloat16 in and out, float32 between: where the rotation is exact in
    float32 (position 0 turns nothing; small integers times a table of 0,
    1 and -1 elsewhere) the pass hands back the operands' own bits, and on
    seeded operands it is the float32 rotation rounded once, as
    `rope.apply` rounds."""
    t, heads = 32, 2
    q, kva, *weights = _raw(t, heads, jnp.bfloat16)
    # (cotangents that bfloat16 holds: the results' are cast to it)
    weights = [w.astype(jnp.bfloat16).astype(jnp.float32) for w in weights]
    got, grads = _out_and_grads(_by_the_pass(t, heads), q, kva, weights)
    assert {x.dtype for x in got + grads} == {jnp.dtype(jnp.bfloat16)}
    raw = q.reshape(2, t, heads, DN + DR)
    np.testing.assert_array_equal(got[0], jnp.moveaxis(raw[..., :DN], 1, 2))
    np.testing.assert_array_equal(got[1][:, :, 0], raw[:, 0, :, DN:])
    np.testing.assert_array_equal(got[2][:, 0], kva[:, 0, RANK:])
    exact = [x.astype(jnp.float32) for x in (q, kva)]
    want, want_grads = _out_and_grads(_by_jax_numpy(t, heads), *exact,
                                      weights)
    for a, b in zip(got + grads, want + want_grads):
        # (the two float32 sums differ in their last bit here and there,
        # which one element in ten thousand shows after the rounding)
        assert np.mean(np.asarray(a != b.astype(jnp.bfloat16))) < 1e-3
        assert rel_gap(a.astype(jnp.float32), b) < 3e-3


def test_the_pass_under_checkpoint_is_the_pass():
    """Under `jax.checkpoint` that keeps nothing (a decoder layer's policy
    keeps only the attention kernel's results) the forward runs again in the
    backward pass and hands back the same numbers."""
    q, kva, *weights = _raw(160, 4, seed=3)
    f = _by_the_pass(160, 4)
    _, grads = _out_and_grads(f, q, kva, weights)
    _, again = _out_and_grads(jax.checkpoint(f), q, kva, weights)
    for a, b in zip(grads, again):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("t,block_q,block_k", [
    (256, 128, 128), (384, 128, 128), (512, 128, 256), (512, 256, 128),
    (1024, None, None)])
def test_the_addressed_entry_is_the_entry(t, block_q, block_k):
    """`flash_attention_latent_laid` on q where the pass writes it and kv,
    o and dO where the projections write and read them is
    `flash_attention_latent` on [B, T, H, D], to the bit: the output and the
    five gradients (dk_nope and dv the two halves of a head's block of
    kv_b_proj's cotangent, dk_r the sum over the heads)."""
    heads = 3
    (qn, qr, kn, kr, v), weight = operands(t, heads=heads, dn=DN, dr=DR,
                                           dv=DV)
    kv = jnp.concatenate([kn, v], axis=-1).reshape(2, t, -1)
    blocks = dict(block_q=block_q, block_k=block_k)

    def entry(qn, qr, kn, kr, v):
        out = flash_attention_latent(qn, qr, kn, kr, v, **blocks)
        return jnp.sum(out * weight), out

    def addressed(qn, qr, kv, kr):
        out = flash_attention_latent_laid(qn, qr, kv, kr, **blocks)
        return jnp.sum(out * weight.reshape(out.shape)), out
    (_, want), gw = jax.value_and_grad(entry, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(qn, qr, kn, kr, v)
    (_, got), gg = jax.value_and_grad(addressed, argnums=(0, 1, 2, 3),
                                      has_aux=True)(
        jnp.moveaxis(qn, 1, 2), jnp.moveaxis(qr, 1, 2), kv, kr)
    assert got.shape == (2, t, heads * DV)
    np.testing.assert_array_equal(got, want.reshape(got.shape))
    np.testing.assert_array_equal(jnp.moveaxis(gg[0], 1, 2), gw[0])
    np.testing.assert_array_equal(jnp.moveaxis(gg[1], 1, 2), gw[1])
    dkv = gg[2].reshape(2, t, heads, DN + DV)
    np.testing.assert_array_equal(dkv[..., :DN], gw[2])
    np.testing.assert_array_equal(dkv[..., DN:], gw[4])
    np.testing.assert_array_equal(gg[3], gw[3])


@pytest.mark.parametrize("change,why", [
    (dict(t=160), "padded to 256"),
    (dict(dn=64), "whole lane tiles"),
    (dict(kr_heads=True), "not one latent attention's operands")])
def test_the_addressed_entry_refuses_by_name(change, why):
    t, dn = change.get("t", 256), change.get("dn", DN)
    qn, qr = jnp.zeros((2, 2, t, dn)), jnp.zeros((2, 2, t, DR))
    kr = jnp.zeros((2, t, 2, DR) if change.get("kr_heads") else (2, t, DR))
    with pytest.raises(ValueError, match=why):
        flash_attention_latent_laid(qn, qr, jnp.zeros((2, t, 2 * (dn + DV))),
                                    kr)


WIDE = dict(num_heads=2, q_rank=96, kv_rank=RANK, nope_dim=DN, rope_dim=DR,
            v_dim=DV, rope_parameters=THETA)


def _wide(flash, dtype=jnp.float32):
    return LatentAttention(**WIDE, flash=flash, dtype=dtype)


@pytest.mark.parametrize("t,kernel", [(256, "pallas"), (160, "jax.numpy")])
def test_the_module_with_the_kernels_is_its_xla_path(t, kernel):
    """`LatentAttention` at widths the plan takes with `flash` on (the pass,
    then the kernels on addressed operands; at a length a pass would pad,
    the `jax.numpy` form and the padding entry, by the reason's name)
    against its XLA path: the output and every gradient."""
    xla, kernels = _wide(False), _wide(True)
    plan = kernels.latent_plan(2, t, True)
    assert plan["kernel"] == kernel
    assert ("padded" in plan["reason"]) if kernel == "jax.numpy" else (
        "reason" not in plan)
    u, weight = inputs(t)
    params = xla.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, HIDDEN)))
    params = jax.tree_util.tree_map(
        lambda x: x * (8.0 if x.ndim == 2 else 1.0), params)

    def loss(model):
        return lambda p, x: jnp.sum(model.apply(p, x) * weight)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(loss(xla), argnums=(0, 1))(
            params, u)
        got, grads = jax.value_and_grad(loss(kernels), argnums=(0, 1))(
            params, u)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads)):
        assert rel_gap(g, w) < 2e-5, jax.tree_util.keystr(path)


def test_the_module_in_bfloat16_is_near_its_xla_path():
    xla, kernels = _wide(False, jnp.bfloat16), _wide(True, jnp.bfloat16)
    u, _ = inputs(256)
    params = xla.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, HIDDEN)))
    params = jax.tree_util.tree_map(
        lambda x: x * (8.0 if x.ndim == 2 else 1.0), params)
    got, want = kernels.apply(params, u), xla.apply(params, u)
    assert got.dtype == jnp.bfloat16
    assert rel_gap(got.astype(jnp.float32), want.astype(jnp.float32)) < 2e-2


def test_initialisation_takes_the_jax_numpy_form():
    """Initialisation runs the XLA path on a short row whatever `flash`
    says, and its parameters are the XLA path's, names and shapes."""
    x = jnp.zeros((1, 16, HIDDEN))
    a = _wide(True).init(jax.random.PRNGKey(0), x)
    b = _wide(False).init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for p, q in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(p, q)


# (rows, positions, heads, unrotated, rotated, value columns, kv_rank)
CELL = (2, 8192, 32, 128, 64, 128, 512)


@pytest.mark.parametrize("shape,flash,word", [
    (CELL, True, None),
    ((1, 1024, 2, 256, 64, 128, 128), True, None),
    ((2, 8192, 32, 128, 128, 128, 512), True, None),
    (CELL, False, "kernels do not run"),
    ((2, 32, 4, 16, 8, 16, 32), True, "no whole numbers of lane tiles"),
    ((2, 8192, 32, 128, 64, 64, 512), True, "no whole numbers of lane tiles"),
    ((2, 8192, 32, 128, 32, 128, 512), True, "no whole lane tile"),
    ((2, 8192, 32, 128, 63, 128, 512), True, "no whole lane tile"),
    ((2, 8192, 32, 128, 64, 128, 576), True, "inside a lane tile"),
    ((2, 8192, 3, 128, 64, 128, 512), True, "no whole number of head pairs"),
    ((2, 8000, 32, 128, 64, 128, 512), True, "do not tile a row of 8000"),
    ((2, 37, 32, 128, 64, 128, 512), True, "do not tile a row of 37"),
    ((2, 8704, 32, 128, 64, 128, 512), True, "8704 positions is padded"),
    ((2, 160, 32, 128, 64, 128, 512), True, "160 positions is padded")])
def test_which_shapes_take_the_pass(shape, flash, word):
    plan = latent_plan(*shape, flash=flash)
    if word is None:
        rows, t, heads = shape[:3]
        per = min(512, t)
        assert plan == dict(kernel="pallas", rows_per_program=per,
                            programs=rows * (t // per) * (
                                heads // min(8, heads)))
    else:
        assert plan["kernel"] == "jax.numpy" and word in plan["reason"], plan
