"""The Mamba-2 mixer's convolution pass (``tpudist/ops/pallas/
causal_conv.py``, interpreted here) against ``jax.nn.silu(ssd.
causal_conv1d(...))`` split by ``jax.numpy`` and JAX's own gradients of it:
the three results and all three cotangents; a row's start and a time
block's; which shapes take which program, and what the model and the
trainer say of it."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops import ssd
from tpudist.ops.pallas.causal_conv import conv_silu_split

# small and lane-aligned: x of two blocks of 256, B and C of two of 128
# behind 512 columns of a gate, a ragged tail of 64 (dt) after them
WIDTHS, OFFSET, TAIL, TAPS = (512, 256, 256), 512, 64, 4
NAMES = ("src", "kernel", "bias")


def _inputs(t, *, rows=2, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3 + len(WIDTHS))
    c = sum(WIDTHS)
    return dict(
        src=jax.random.normal(keys[0], (rows, t, OFFSET + c + TAIL)
                              ).astype(dtype),
        kernel=jax.random.uniform(keys[1], (TAPS, c), minval=-0.5,
                                  maxval=0.5),
        bias=jax.random.uniform(keys[2], (c,), minval=-0.5, maxval=0.5),
        w=[jax.random.normal(k, (rows, t, w)).astype(dtype)
           for k, w in zip(keys[3:], WIDTHS)])


def _by_numpy(src, kernel, bias):
    """What the pass is held to: the module's own ``jax.numpy`` form."""
    xbc = src[..., OFFSET:OFFSET + sum(WIDTHS)]
    y = jax.nn.silu(ssd.causal_conv1d(xbc, kernel, bias)).astype(src.dtype)
    return tuple(jnp.split(y, list(np.cumsum(WIDTHS)[:-1]), axis=-1))


def _by_pass(rows_per_program):
    def f(src, kernel, bias):
        return conv_silu_split(src, kernel, bias, offset=OFFSET,
                               widths=WIDTHS, rows=rows_per_program)
    return f


def _results_and_grads(f, v, wrap=lambda f: f):
    def loss(*args):
        outs = f(*args)
        return sum(jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))
                   for o, w in zip(outs, v["w"])), outs
    (_, outs), grads = jax.value_and_grad(
        wrap(loss), argnums=range(3), has_aux=True)(*(v[k] for k in NAMES))
    return outs, grads


def _close(got, want, tol, what):
    for name, g, w in zip(what, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * float(np.abs(w).max()),
                                   err_msg=name)


# several time blocks a row, one block a row, the smallest block there is
@pytest.mark.parametrize("t,per", [(192, 64), (128, 128), (48, 16)])
def test_pass_is_the_numpy_form_in_float32(t, per):
    v = _inputs(t, seed=t)
    outs, grads = _results_and_grads(_by_pass(per), v)
    want, want_grads = _results_and_grads(_by_numpy, v)
    assert [o.shape for o in outs] == [(2, t, w) for w in WIDTHS]
    _close(outs, want, 1e-6, "xBC")
    _close(grads, want_grads, 1e-5, NAMES)
    # the source's other columns took no part
    d = np.asarray(grads[0])
    assert not d[..., :OFFSET].any() and not d[..., -TAIL:].any()


def test_pass_takes_bfloat16_activations_and_float32_taps():
    v = _inputs(192, seed=3, dtype=jnp.bfloat16)
    outs, grads = _results_and_grads(_by_pass(64), v)
    want, want_grads = _results_and_grads(_by_numpy, v)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32,
                                        jnp.float32]
    # float32 sums and SiLU of the same bfloat16 values, one rounding: the
    # results are the numpy form's to the last bit but where a float32 sum
    # lands on a rounding's edge
    for o, w in zip(outs, want):
        assert o.dtype == jnp.bfloat16
        assert float(jnp.mean(o != w)) < 1e-3
    _close(outs, want, 8e-3, "xBC")

    def err(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        return float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
    # JAX's transpose rounds du to bfloat16 a tap; the pass rounds d x once
    assert err(grads[0], want_grads[0]) < 5e-3
    assert err(grads[1], want_grads[1]) < 5e-3
    assert err(grads[2], want_grads[2]) < 5e-3
    # and nearer the float32 gradient of the rounded inputs than that is
    exact = _results_and_grads(_by_numpy, dict(
        v, src=v["src"].astype(jnp.float32),
        w=[w.astype(jnp.float32) for w in v["w"]]))[1]
    assert err(grads[0], exact[0]) < 4e-3
    for i in (1, 2):
        assert err(grads[i], exact[i]) <= err(want_grads[i], exact[i]) + 1e-6


def test_a_row_starts_from_zeros_and_sees_no_other_row():
    """Three blocks a row: a row's first ``taps - 1`` positions read zeros
    before them (not the row above's end, which lies just before in
    memory), and nothing of one row reaches another, forward or back."""
    v = _inputs(96, rows=3, seed=5)
    f = _by_pass(32)
    outs = f(*(v[k] for k in NAMES))
    # the first positions by hand: bias + the taps that reach real positions
    xbc = v["src"][..., OFFSET:OFFSET + sum(WIDTHS)]
    for i in range(TAPS - 1):
        u = v["bias"] + sum(
            v["kernel"][TAPS - 1 - back] * xbc[:, i - back]
            for back in range(i + 1))
        np.testing.assert_allclose(
            np.concatenate([o[:, i] for o in outs], -1), jax.nn.silu(u),
            rtol=1e-6, atol=1e-6)
    # row 1 changed: rows 0 and 2 as they were, to the bit
    moved = f(v["src"].at[1].add(1.0), v["kernel"], v["bias"])
    for o, m in zip(outs, moved):
        assert bool(jnp.all(o[0] == m[0])) and bool(jnp.all(o[2] == m[2]))
        assert bool(jnp.any(o[1] != m[1]))
    # a cotangent on row 1 alone reaches row 1's source alone
    only = dict(v, w=[w.at[0].set(0).at[2].set(0) for w in v["w"]])
    d = _results_and_grads(f, only)[1][0]
    assert not np.asarray(d[0]).any() and not np.asarray(d[2]).any()
    assert np.asarray(d[1]).any()


def test_a_time_blocks_first_rows_see_the_block_before():
    """Four blocks of 32: positions 32 to 34 read the last three of block
    0, and d x of positions 29 to 31 what block 1's first three hand back;
    the same numbers whatever the block (16, 32, 64 or the whole row)."""
    v = _inputs(128, rows=1, seed=6)
    by_block = {per: _results_and_grads(_by_pass(per), v)
                for per in (16, 32, 64, 128)}
    outs, grads = by_block[128]
    for per in (16, 32, 64):
        _close(by_block[per][0], outs, 1e-6, "xBC")
        _close(by_block[per][1], grads, 2e-6, NAMES)
    # position 31 moved: positions 31 to 34 follow, 35 does not
    f = _by_pass(32)
    moved = f(v["src"].at[:, 31].add(1.0), v["kernel"], v["bias"])
    base = f(*(v[k] for k in NAMES))
    for o, m in zip(base, moved):
        changed = np.asarray(jnp.any(o != m, axis=(0, 2)))
        assert changed[31:35].all() and not changed[:31].any()
        assert not changed[35:].any()
    # a cotangent on position 33 alone reaches d x of positions 30 to 33
    only = dict(v, w=[jnp.zeros_like(w).at[:, 33].set(1.0) for w in v["w"]])
    d = np.asarray(_results_and_grads(f, only)[1][0])
    reached = np.abs(d).sum(axis=(0, 2)) > 0
    assert reached[30:34].all() and not reached[:30].any()
    assert not reached[34:].any()


def test_pass_under_checkpoint_is_the_pass():
    """A block is rematerialised whole: the forward runs twice, the backward
    once, and nothing but the source is kept between them."""
    v = _inputs(96, rows=1, seed=7)
    f = _by_pass(32)
    outs, grads = _results_and_grads(f, v)
    again_outs, again = _results_and_grads(f, v, wrap=jax.checkpoint)
    _close(again_outs, outs, 1e-7, "xBC")
    _close(again, grads, 1e-7, NAMES)
    # (a loss whose transpose reads the results, as the scan's does: the
    # pass's own backward reads the source alone)
    text = str(jax.make_jaxpr(jax.grad(jax.checkpoint(
        lambda src: sum(jnp.sum(jnp.square(o)) for o in f(
            src, v["kernel"], v["bias"])))))(v["src"]))
    assert text.count("pallas_call") == 3


def test_ssd_takes_the_pass_where_the_plan_says(monkeypatch):
    """``ssd.conv_silu_split`` is the mixer's one call: the pass at whole
    lane tiles, the ``jax.numpy`` form otherwise, the same numbers."""
    v = _inputs(64, seed=8)
    args = [v[k] for k in NAMES]
    assert ssd.conv_plan(*v["src"].shape, WIDTHS, TAPS, OFFSET) == dict(
        kernel="pallas", rows_per_program=64, programs=4)
    text = str(jax.make_jaxpr(
        lambda *a: ssd.conv_silu_split(*a, OFFSET, WIDTHS))(*args))
    assert text.count("pallas_call") == 1
    got = ssd.conv_silu_split(*args, OFFSET, WIDTHS)
    plan = ssd.conv_plan
    monkeypatch.setattr(ssd, "conv_plan", lambda *shape: dict(
        plan(*shape), kernel="jax.numpy"))
    text = str(jax.make_jaxpr(
        lambda *a: ssd.conv_silu_split(*a, OFFSET, WIDTHS))(*args))
    assert "pallas_call" not in text
    _close(got, ssd.conv_silu_split(*args, OFFSET, WIDTHS), 1e-6, "xBC")
    _close(got, _by_numpy(*args), 1e-6, "xBC")


PUBLISHED = (2, 8192, 10304, (4096, 1024, 1024), 4, 4096)


@pytest.mark.parametrize("shape,word", [
    # the published model's, at the cell's rows and length: eight channel
    # blocks of 512 + 128 + 128 lanes, sixteen time blocks of 512
    (PUBLISHED, None),
    # xBC sliced first (columns from 0) takes it too
    ((2, 8192, 6144, (4096, 1024, 1024), 4, 0), None),
    # the tiny twin's: 64 + 2 x 32 channels behind a gate of 64
    ((16, 32, 200, (64, 32, 32), 4, 64), "a width of 64"),
    ((2, 8192, 10304, (4096, 1024, 1000), 4, 4096), "a width of 1000"),
    # x would start in the middle of one of its blocks of 512
    ((2, 8192, 10304, (4096, 1024, 1024), 4, 4000), "columns from 4000"),
    ((2, 8192, 10432, (4096, 1024, 1024), 4, 4224), "columns from 4224"),
    ((2, 8200, 10304, (4096, 1024, 1024), 4, 4096), "tiles a row of 8200"),
    ((2, 8192, 10304, (4096, 1024, 1024), 9, 4096), "9 taps")])
def test_which_shapes_take_the_pass(shape, word):
    plan = ssd.conv_plan(*shape)
    if word is None:
        assert plan == dict(kernel="pallas", rows_per_program=512,
                            programs=2 * 8 * 16)
    else:
        assert plan["kernel"] == "jax.numpy" and word in plan["reason"], plan
        assert plan["programs"] == 1


def test_plan_fits_the_time_block_to_the_lanes_and_the_length():
    # one lane tile a result: 384 lanes a program, a thousand positions
    assert ssd.conv_plan(1, 2048, 384, (128, 128, 128), 4, 0) == dict(
        kernel="pallas", rows_per_program=1024, programs=2)
    # widths that share no block count: one channel block of 4,096 lanes
    assert ssd.conv_plan(1, 2048, 4096, (3840, 128, 128), 4, 0) == dict(
        kernel="pallas", rows_per_program=64, programs=32)
    # a length that only small blocks divide
    assert ssd.conv_plan(3, 48, 768, (512, 128, 128), 4, 0) == dict(
        kernel="pallas", rows_per_program=16, programs=9)
    with pytest.raises(ValueError, match="columns 4096 to 10240 of 10000"):
        ssd.conv_plan(2, 8192, 10000, (4096, 1024, 1024), 4, 4096)


def test_models_state_their_plan_and_the_trainer_announces_it(tmp_path):
    from tpudist import telemetry
    from tpudist.models import create_model
    from tpudist.trainer import Trainer
    published = create_model("nemotron3_nano_30b_a3b", layers=9)
    assert published.conv_plan(2, 8192) == ssd.conv_plan(*PUBLISHED)
    tiny = create_model("nemotron3_tiny", layers=4)
    plan = tiny.conv_plan(16, 32)
    assert plan["kernel"] == "jax.numpy" and plan["rows_per_program"] == 32
    # a decoder without the mixer has no plan
    assert create_model("mellum2_tiny").conv_plan(16, 32) is None
    lines = []
    sink = telemetry.Telemetry(str(tmp_path), heartbeat=False)
    fake = types.SimpleNamespace(log=lines.append, telemetry=sink)
    for p in (published.conv_plan(2, 8192), plan, None):
        if p is not None:           # `MoEDecoder.plans` leaves it out
            Trainer._announce_plan(fake, "ssm_conv", p)
    sink.close()
    assert lines == [
        "=> ssm_conv: pallas (rows_per_program 512, programs 256)",
        "=> ssm_conv: jax.numpy (rows_per_program 32, programs 1: a width "
        "of 64 is no whole number of lane tiles)"]
    with open(telemetry.events_path(str(tmp_path), 0)) as f:
        events = [e for e in map(json.loads, f) if e["type"] == "ssm_conv"]
    assert [e["kernel"] for e in events] == ["pallas", "jax.numpy"]
    assert set(telemetry.SCHEMA["ssm_conv"]) <= set(events[0])
    assert "reason" in events[1] and "reason" not in events[0]


def test_the_mixer_runs_the_pass_at_whole_lane_tiles():
    """A mixer of two heads of 64 and one group with a state of 128 (x, B
    and C of one lane tile each behind a gate of one): its convolution is
    the pass, its outputs and gradients the ``jax.numpy`` form's."""
    from tpudist.models.decoder import Mamba2Mixer
    mixer = Mamba2Mixer(num_heads=2, head_dim=64, state=128, groups=1,
                        conv=4, chunk=8, time_step=(0.001, 0.1, 1e-4))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    params = mixer.init(jax.random.PRNGKey(1), x)
    assert ssd.conv_plan(2, 32, 2 * 128 + 2 * 128 + 2, (128, 128, 128), 4,
                         128)["kernel"] == "pallas"

    def loss(params, x):
        return jnp.sum(jnp.square(mixer.apply(params, x)[0]))
    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    assert text.count("pallas_call") == 2
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    plan = ssd.conv_plan
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd, "conv_plan", lambda *shape: dict(
            plan(*shape), kernel="jax.numpy"))
        assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(loss))(
            params, x))
        want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    flat, want_flat = jax.tree.leaves(got), jax.tree.leaves(want)
    _close(flat, want_flat, 2e-5, [str(i) for i in range(len(flat))])
