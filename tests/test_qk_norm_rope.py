"""q / k RMSNorm and RoPE as one Pallas pass
(``tpudist/ops/pallas/qk_norm_rope.py``, interpreted here) against the
``jax.numpy`` form it stands for (``RMSNorm`` + ``rope.apply`` + the move
into the attention kernels' layout): values and the cotangents of raw q, raw
k and both norm scales; ``GroupedQueryAttention`` with the streaming kernels
against the XLA path; which shapes take which program, and what the trainer
says of it."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.decoder import GroupedQueryAttention
from tpudist.ops import rope
from tpudist.ops.pallas.flash_attention import _operand
from tpudist.ops.pallas.qk_norm_rope import qk_norm_rope, qk_plan

B, T, D, EPS = 2, 512, 128, 1e-6
PLAIN = {"rope_type": "default", "rope_theta": 500000}
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
TWICE = np.concatenate([np.arange(T // 2), np.arange(T // 2)])
# (rope parameters, positions, norm): what a layer's fields can say
LAYERS = {
    "plain": (PLAIN, T, True), "yarn": (YARN, T, True),
    "positions_twice": (PLAIN, TWICE, True),
    "norm_without_rotation": (None, T, True),
    "rotation_without_norm": (PLAIN, T, False)}


def _inputs(heads, kv_heads, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    group = heads // kv_heads
    return dict(
        q=jax.random.normal(keys[0], (B, T, heads * D), dtype),
        k=jax.random.normal(keys[1], (B, T, kv_heads * D), dtype) * 3,
        q_scale=1 + 0.2 * jax.random.normal(keys[2], (D,)),
        k_scale=1 + 0.2 * jax.random.normal(keys[3], (D,)),
        wq=jax.random.normal(keys[4], (B, kv_heads, group, T, D)),
        wk=jax.random.normal(keys[5], (B, kv_heads, 1, T, D)))


def _by_jax_numpy(kv_heads, params, positions, norm):
    """The lines ``GroupedQueryAttention`` runs where the pass does not
    apply, then ``flash_attention``'s own move."""
    tables = params and rope.tables(params, D, positions)

    def one(x, scale):
        dt = x.dtype
        x = x.reshape(B, T, -1, D)
        if norm:
            x32 = x.astype(jnp.float32)
            x = (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                              keepdims=True) + EPS)
                 * scale).astype(dt)
        if tables:
            x = rope.apply(x, *tables)
        return _operand(x, kv_heads, T)
    return lambda q, k, qs, ks: (one(q, qs), one(k, ks))


def _by_the_pass(kv_heads, params, positions, norm):
    cos, sin = rope.tables(params, D, positions) if params else (None, None)

    def f(q, k, qs, ks):
        scales = dict(q_scale=qs, k_scale=ks) if norm else {}
        return qk_norm_rope(q, k, kv_heads=kv_heads, cos=cos, sin=sin,
                            eps=EPS, **scales)
    return f


def _out_and_grads(f, v):
    def loss(*args):
        ql, kl = f(*args)
        return (jnp.sum(ql.astype(jnp.float32) * v["wq"])
                + jnp.sum(kl.astype(jnp.float32) * v["wk"])), (ql, kl)
    (_, out), grads = jax.value_and_grad(loss, argnums=range(4),
                                         has_aux=True)(
        v["q"], v["k"], v["q_scale"], v["k_scale"])
    return out, grads


def _gap(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want)
                 / (np.linalg.norm(want) + 1e-30))


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("heads,kv_heads", [(32, 4), (4, 4)],
                         ids=["group_of_8", "group_of_1"])
def test_pass_is_the_jax_numpy_form_in_float32(heads, kv_heads, layer):
    params, positions, norm = LAYERS[layer]
    v = _inputs(heads, kv_heads, jnp.float32)
    out, grads = _out_and_grads(
        _by_the_pass(kv_heads, params, positions, norm), v)
    want, want_grads = _out_and_grads(
        _by_jax_numpy(kv_heads, params, positions, norm), v)
    assert out[0].shape == (B, kv_heads, heads // kv_heads, T, D)
    assert out[1].shape == (B, kv_heads, 1, T, D)
    for name, got, w in zip(("q", "k"), out, want):
        np.testing.assert_allclose(got, w, atol=2e-5, err_msg=name)
    names = ("dq", "dk", "dq_scale", "dk_scale")
    for name, got, w in zip(names, grads, want_grads):
        assert got.shape == w.shape and got.dtype == w.dtype, name
        if norm or "scale" not in name:
            assert _gap(got, w) < 2e-5, (name, _gap(got, w))
        else:
            assert not np.any(np.asarray(got)), name


@pytest.mark.parametrize("heads,kv_heads", [(32, 4), (4, 4)],
                         ids=["group_of_8", "group_of_1"])
def test_pass_rounds_once_where_the_jax_numpy_form_rounds_twice(heads,
                                                                kv_heads):
    """bfloat16 in and out, float32 between: against the float32 form the
    pass is the closer of the two (one rounding, at the end), and each is
    within a rounding or two of it."""
    v = _inputs(heads, kv_heads, jnp.bfloat16)
    exact = {k: x.astype(jnp.float32) for k, x in v.items()}
    out, grads = _out_and_grads(_by_the_pass(kv_heads, YARN, T, True), v)
    twice, twice_grads = _out_and_grads(
        _by_jax_numpy(kv_heads, YARN, T, True), v)
    want, want_grads = _out_and_grads(
        _by_jax_numpy(kv_heads, YARN, T, True), exact)
    assert out[0].dtype == out[1].dtype == jnp.bfloat16
    assert grads[0].dtype == grads[1].dtype == jnp.bfloat16
    for got, two, w in zip(out + grads, twice + twice_grads,
                           want + want_grads):
        assert _gap(got, w) < 3e-3
        assert _gap(got, w) <= _gap(two, w) * 1.01


def test_pass_under_checkpoint_is_the_pass():
    """Under ``jax.checkpoint`` that keeps nothing (a decoder layer's policy
    keeps only the attention kernel's results) the forward runs again in the
    backward pass and hands back the same numbers."""
    v = _inputs(8, 2, jnp.float32, seed=3)
    f = _by_the_pass(2, PLAIN, T, True)
    _, grads = _out_and_grads(f, v)
    _, again = _out_and_grads(jax.checkpoint(f), v)
    for a, b in zip(grads, again):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("mask", [
    dict(causal=True), dict(causal=True, window=128),
    dict(block_diffusion=(T // 2, 4))],
    ids=["full", "windowed", "block_diffusion"])
def test_laid_operands_run_the_kernels_flash_attention_runs(mask):
    """``flash_attention_laid`` on q and k moved by ``_operand`` is
    ``flash_attention`` on [B, T, H, D], to the bit: the output, dV, and dQ
    and dK as the kernels wrote them (laid)."""
    from tpudist.ops.pallas import flash_attention, flash_attention_laid
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (B, T, 8, D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (B, T, 2, D), jnp.bfloat16)
    v = jax.random.normal(keys[2], (B, T, 2, D), jnp.bfloat16)
    w = jax.random.normal(keys[3], (B, T, 8, D))

    def loss(f, *args):
        out = f(*args, **mask)
        return jnp.sum(out.astype(jnp.float32) * w), out
    (_, want), (dq, dk, dv) = jax.value_and_grad(
        lambda *a: loss(flash_attention, *a), argnums=(0, 1, 2),
        has_aux=True)(q, k, v)
    (_, got), (dql, dkl, dvl) = jax.value_and_grad(
        lambda *a: loss(flash_attention_laid, *a), argnums=(0, 1, 2),
        has_aux=True)(_operand(q, 2, T), _operand(k, 2, T), v)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(dql, _operand(dq, 2, T))
    np.testing.assert_array_equal(dkl, _operand(dk, 2, T))
    np.testing.assert_array_equal(dvl, dv)


@pytest.mark.parametrize("t,heads,mask,why", [
    (8704, 8, dict(causal=True), "8704 positions is padded"),
    (512, 32, dict(causal=True), "a group of 16"),
    (512, 8, dict(window=128), "not one self-attention")],
    ids=["padded", "split_group", "window_without_causal"])
def test_laid_operands_are_refused_by_name(t, heads, mask, why):
    from tpudist.ops.pallas import flash_attention_laid
    q = jax.ShapeDtypeStruct((1, 2, heads // 2, t, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, 1, t, D), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, t, 2, D), jnp.bfloat16)
    with pytest.raises(ValueError, match=why):
        jax.eval_shape(lambda *a: flash_attention_laid(*a, **mask), q, k, v)


def _attention(flash, **fields):
    return GroupedQueryAttention(
        num_heads=8, num_kv_heads=2, head_dim=D, dtype=jnp.float32,
        flash=flash, **fields)


@pytest.mark.parametrize("fields", [
    dict(rope_parameters=PLAIN),
    dict(rope_parameters=YARN, window=128),
    dict(rope_parameters=PLAIN, block_diffusion=(T // 2, 4)),
    dict(rope_parameters=None),
    dict(rope_parameters=PLAIN, qk_norm=False)],
    ids=["full", "windowed", "block_diffusion", "no_rotation", "no_norm"])
def test_attention_with_the_kernels_is_the_xla_path(fields):
    """``GroupedQueryAttention`` with ``flash`` on (the pass, then the
    streaming kernels on laid operands) against the XLA path: the output and
    every gradient within ``tests/test_decoder.py``'s tolerances."""
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (B, T, 64))
    xla, kernels = _attention(False, **fields), _attention(True, **fields)
    assert kernels.qk_plan(B, T, True)["kernel"] == "pallas"
    params = xla.init(jax.random.PRNGKey(0), x)
    # a norm's weight that is not all ones, so that its cotangent counts
    params = jax.tree_util.tree_map_with_path(
        lambda path, p: p + 0.1 * jnp.cos(jnp.arange(p.size, dtype=p.dtype))
        .reshape(p.shape) if "norm" in jax.tree_util.keystr(path) else p,
        params)

    def loss(model):
        # (a sum of squares: a loss that does not cancel to nothing)
        return lambda p, x: jnp.mean(jnp.square(model.apply(p, x) + w))
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(loss(xla), argnums=(0, 1))(
            params, x)
        got, grads = jax.value_and_grad(loss(kernels), argnums=(0, 1))(
            params, x)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for (path, g), (_, wg) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads)):
        assert _gap(g, wg) < 2e-4, (jax.tree_util.keystr(path), _gap(g, wg))


def test_initialisation_takes_the_jax_numpy_form():
    """Initialisation runs the XLA path on a short row whatever ``flash``
    says, and its parameters are the XLA path's, names and shapes."""
    x = jnp.zeros((1, 16, 64))
    a = _attention(True, rope_parameters=PLAIN).init(jax.random.PRNGKey(0), x)
    b = _attention(False, rope_parameters=PLAIN).init(jax.random.PRNGKey(0),
                                                      x)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for p, q in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(p, q)


PUBLISHED = dict(norm=True, rotate=True, flash=True)


@pytest.mark.parametrize("shape,fields,word", [
    # the two claimed cells' shapes: windowed and full, then the doubled row
    ((2, 8192, 32, 4, 128), dict(PUBLISHED, window=1024), None),
    ((2, 8192, 32, 4, 128), PUBLISHED, None),
    ((2, 16384, 32, 4, 128), dict(PUBLISHED, block_diffusion=(8192, 4)),
     None),
    # a group of one, a head of two lane tiles
    ((1, 1024, 4, 4, 256), PUBLISHED, None),
    ((2, 8192, 32, 4, 64), PUBLISHED, "a head of 64"),
    ((2, 32, 8, 2, 16), PUBLISHED, "a head of 16"),
    ((2, 8000, 32, 4, 128), PUBLISHED, "do not tile a row of 8000"),
    ((2, 8704, 32, 4, 128), PUBLISHED, "8704 positions is padded"),
    ((2, 16384, 32, 4, 128), dict(PUBLISHED, block_diffusion=(0, 4)),
     "is padded"),
    ((2, 8192, 32, 2, 128), dict(PUBLISHED, norm=False, rotate=False),
     "neither norms nor rotates"),
    ((2, 8192, 32, 2, 128), PUBLISHED, "a group of 16"),
    ((2, 8192, 32, 4, 128), dict(PUBLISHED, flash=False),
     "kernels do not run")])
def test_which_shapes_take_the_pass(shape, fields, word):
    plan = qk_plan(*shape, **fields)
    if word is None:
        rows, t, _, kv_heads, _ = shape
        assert plan == dict(kernel="pallas", rows_per_program=512,
                            programs=rows * kv_heads * t // 512)
    else:
        assert plan["kernel"] == "jax.numpy" and word in plan["reason"], plan


def test_models_state_their_plans_and_the_trainer_announces_them(tmp_path):
    from tpudist import telemetry
    from tpudist.models import create_model
    from tpudist.trainer import Trainer
    share = dict(layers=4, flash=True)
    # both of mellum2's layer types take the pass, as one plan
    mellum2 = create_model("mellum2_12b_a2_5b", **share).qk_plans(2, 8192)
    sdar = create_model("sdar_30b_a3b", **share).qk_plans(2, 8192)
    hybrid = create_model("nemotron3_nano_30b_a3b", layers=9,
                          flash=True).qk_plans(2, 8192)
    tiny = create_model("mellum2_tiny", flash=True).qk_plans(16, 32)
    # latent attention: one plan for the five layers and the MTP module's
    # block (`latent_rope.latent_plan`), at the cell's shape and at a length
    # a pass would pad
    latent = create_model("joyai_llm_flash", layers=5, flash=True)
    joyai, padded = latent.qk_plans(2, 8192), latent.qk_plans(2, 8704)
    assert joyai == [dict(kernel="pallas", rows_per_program=512,
                          programs=128)]
    assert [p["kernel"] for p in padded] == ["jax.numpy"]
    assert mellum2 == [dict(kernel="pallas", rows_per_program=512,
                            programs=128)]
    assert sdar == [dict(kernel="pallas", rows_per_program=512,
                         programs=256)]
    assert [p["kernel"] for p in hybrid + tiny] == ["jax.numpy"] * 2
    # a share that keeps no attention has no plan
    assert create_model("nemotron3_tiny", layers=2).qk_plans(16, 32) == []
    lines = []
    sink = telemetry.Telemetry(str(tmp_path), heartbeat=False)
    fake = types.SimpleNamespace(log=lines.append, telemetry=sink)
    for plan in mellum2 + hybrid + tiny + joyai + padded:
        Trainer._announce_plan(fake, "attn_qk", plan)
    sink.close()
    assert lines == [
        "=> attn_qk: pallas (rows_per_program 512, programs 128)",
        "=> attn_qk: jax.numpy (rows_per_program 512, programs 64: the layer "
        "neither norms nor rotates q and k)",
        "=> attn_qk: jax.numpy (rows_per_program 32, programs 32: a head of "
        "16 is no whole number of lane tiles)",
        "=> attn_qk: pallas (rows_per_program 512, programs 128)",
        "=> attn_qk: jax.numpy (rows_per_program 512, programs 136: a row of "
        "8704 positions is padded to 9216 (blocks of 1024 x 1024))"]
    with open(telemetry.events_path(str(tmp_path), 0)) as f:
        events = [e for e in map(json.loads, f) if e["type"] == "attn_qk"]
    assert [e["kernel"] for e in events] == [
        "pallas", "jax.numpy", "jax.numpy", "pallas", "jax.numpy"]
    assert set(telemetry.SCHEMA["attn_qk"]) <= set(events[0])
    assert "reason" in events[1] and "reason" not in events[0]
