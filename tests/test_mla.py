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
