"""The chunked scan's Pallas kernel pair (``tpudist/ops/pallas/ssd_scan.py``,
interpreted here) against ``ssd.ssd_scan``'s ``jax.numpy`` form AND against
the literal recurrence of ``tests/test_ssd.py``: outputs, ``carry_min`` and
every gradient; the seam ``ssd._running_sum``; which shapes take which
program, and what the trainer says of it."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ssd import recurrence
from tpudist.ops import ssd

# small and lane-aligned: 2 groups of 2 heads of 64, a state of 128, chunks
# of the published 128
HEADS, P, GROUPS, N, CHUNK = 4, 64, 2, 128, 128
NAMES = ("x", "dt", "a_log", "b", "c", "d")


def _inputs(t, *, rows=2, seed=0, a_log=None, dt_shift=-2.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(keys[0], (rows, t, HEADS, P)),
        dt=jax.nn.softplus(
            jax.random.normal(keys[1], (rows, t, HEADS)) + dt_shift),
        a_log=(jnp.log(jnp.arange(1, HEADS + 1, dtype=jnp.float32)) - 2.0
               if a_log is None else jnp.asarray(a_log, jnp.float32)),
        b=jax.random.normal(keys[2], (rows, t, GROUPS, N)) / 4,
        c=jax.random.normal(keys[3], (rows, t, GROUPS, N)) / 4,
        d=jax.random.normal(keys[4], (HEADS,)),
        w=jax.random.normal(keys[5], (rows, t, HEADS, P)))


def _scan(x, dt, a_log, b, c, d):
    return ssd.ssd_scan(x, dt, -jnp.exp(a_log), b, c, d, CHUNK)


def _by_fusions(monkeypatch):
    """``ssd_scan`` takes the ``jax.numpy`` form whatever the shape."""
    plan = ssd.scan_plan
    monkeypatch.setattr(ssd, "scan_plan",
                        lambda *shape: dict(plan(*shape), kernel="xla"))


def _loss_and_grads(f, v, wrap=lambda f: f):
    def loss(*args):
        out = f(*args)
        y, rest = out if isinstance(out, tuple) else (out, None)
        return jnp.sum(y * v["w"]), (y, rest)
    (_, (y, rest)), grads = jax.value_and_grad(
        wrap(loss), argnums=range(6), has_aux=True)(*(v[k] for k in NAMES))
    return y, rest, grads


def _close(got, want, tol, what):
    for name, g, w in zip(what, got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * float(np.abs(w).max()),
                                   err_msg=name)


# three whole chunks, a length the chunk does not divide, one shorter than
# a chunk
@pytest.mark.parametrize("t,rows", [(384, 2), (300, 1), (100, 1)])
def test_kernel_is_the_fusions_and_the_recurrence_in_float32(t, rows,
                                                             monkeypatch):
    v = _inputs(t, rows=rows, seed=t)
    assert ssd.scan_plan(rows, t, HEADS, P, GROUPS, N, CHUNK)[
        "kernel"] == "pallas"
    with jax.default_matmul_precision("highest"):
        y, carry_min, grads = _loss_and_grads(_scan, v)
        want_y, _, want = _loss_and_grads(recurrence, v)
        _by_fusions(monkeypatch)
        xla_y, xla_min, xla = _loss_and_grads(_scan, v)
    assert y.shape == (rows, t, HEADS, P) and y.dtype == jnp.float32
    _close([y], [xla_y], 1e-5, ["y"])
    _close(grads, xla, 1e-5, NAMES)
    assert float(carry_min) == float(xla_min)
    _close([y], [want_y], 2e-5, ["y"])
    _close(grads, want, 1e-4, NAMES)        # test_ssd.py's, for the fusions


def test_kernel_takes_bfloat16_products_and_float32_decays(monkeypatch):
    v = _inputs(384, seed=3)
    low = dict(v, **{k: v[k].astype(jnp.bfloat16) for k in ("x", "b", "c")})
    y, _, grads = _loss_and_grads(_scan, low)
    assert y.dtype == jnp.float32
    assert [g.dtype for g in grads] == [
        jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16,
        jnp.float32]
    rounded = dict(v, **{k: low[k].astype(jnp.float32)
                         for k in ("x", "b", "c")})
    want_y, _, want = _loss_and_grads(recurrence, rounded)

    def err(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        return float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
    assert err(y, want_y) < 0.01
    for name, g, w in zip(NAMES, grads, want):
        assert err(g, w) < 0.01, name
    # and no further from the fusions than rounding a cotangent is
    _by_fusions(monkeypatch)
    xla_y, _, xla = _loss_and_grads(_scan, low)
    assert err(y, xla_y) < 1e-3
    for name, g, w in zip(NAMES, grads, xla):
        assert err(g, w) < 0.01, name


def test_state_is_carried_over_chunks_where_it_fades_and_where_it_stays(
        monkeypatch):
    """Four chunks; head 0 forgets within a chunk (``exp(sum of dt A)``
    underflows to 0 in float32), head 3 keeps nearly all of its state (0.98
    a chunk), so its last chunk still reads what the first wrote."""
    v = _inputs(512, rows=1, seed=5, a_log=[4.0, 0.0, -2.0, -9.0],
                dt_shift=1.0)
    with jax.default_matmul_precision("highest"):
        y, carry_min, grads = _loss_and_grads(_scan, v)
        want_y, _, want = _loss_and_grads(recurrence, v)
    per_chunk = jnp.exp(jnp.sum((v["dt"] * -jnp.exp(v["a_log"])).reshape(
        1, 4, CHUNK, HEADS), axis=2))
    assert float(carry_min) == 0.0 == float(jnp.min(per_chunk[..., 0]))
    assert float(jnp.min(per_chunk[..., 3])) > 0.97
    _close([y], [want_y], 2e-5, ["y"])
    _close(grads, want, 1e-4, NAMES)
    # the first chunk's x reaches the last chunk's y through the carried
    # state alone, for the head that keeps it and not for the one that fades
    moved = dict(v, x=v["x"].at[:, :CHUNK].add(1.0))
    with jax.default_matmul_precision("highest"):
        shift = jnp.abs(_scan(*(moved[k] for k in NAMES))[0] - y)[:, -CHUNK:]
    assert float(shift[:, :, 3].max()) > 1e-2
    assert float(shift[:, :, 0].max()) == 0.0


def test_kernel_under_checkpoint_is_the_kernel():
    """A block is rematerialised whole: the forward runs once without the
    entering states and once more, with them, inside the backward."""
    v = _inputs(300, rows=1, seed=6)
    with jax.default_matmul_precision("highest"):
        y, _, grads = _loss_and_grads(_scan, v)
        again_y, _, again = _loss_and_grads(_scan, v, wrap=jax.checkpoint)
    _close([again_y], [y], 1e-6, ["y"])
    _close(again, grads, 1e-6, NAMES)
    text = str(jax.make_jaxpr(jax.grad(jax.checkpoint(
        lambda x: jnp.sum(_scan(x, *(v[k] for k in NAMES[1:]))[0]))))(v["x"]))
    assert text.count("pallas_call") == 3


def test_running_sums_in_bfloat16_move_a_logs_gradient(monkeypatch):
    """``ssd._running_sum`` is still the seam: the benchmark's control
    replaces it and with it rounds every exponent the kernel takes."""
    v = _inputs(384, rows=1, seed=7)
    with jax.default_matmul_precision("highest"):
        _, _, sound = _loss_and_grads(_scan, v)
        monkeypatch.setattr(ssd, "_running_sum", lambda da: jnp.cumsum(
            da.astype(jnp.bfloat16), axis=-1))
        _, _, rounded = _loss_and_grads(_scan, v)

    def gap(name):
        i = NAMES.index(name)
        return float(jnp.linalg.norm(rounded[i] - sound[i])
                     / jnp.linalg.norm(sound[i]))
    assert gap("a_log") > 1e-3
    assert gap("d") < 1e-6            # D x passes no decay


def test_which_shapes_take_the_kernel():
    # the published model's, at the cell's rows and length
    plan = ssd.scan_plan(2, 8192, 64, 64, 8, 128, 128)
    assert plan == dict(kernel="pallas", chunk=128, heads_per_program=8,
                        programs=1024)
    # the tiny twin's and tests/test_ssd.py's
    for shape, word in (((2, 32, 8, 8, 2, 16, 8), "chunk of 8"),
                        ((2, 24, 4, 8, 2, 16, 8), "chunk of 8"),
                        ((2, 256, 64, 64, 8, 16, 128), "state of 16"),
                        ((2, 256, 8, 8, 2, 128, 128), "4 heads of 8"),
                        ((2, 256, 6, 64, 2, 128, 128), "3 heads of 64")):
        plan = ssd.scan_plan(*shape)
        assert plan["kernel"] == "xla" and word in plan["reason"], plan
    # heads of 128 and of 32 are whole tiles too; a ragged length counts
    # its last chunk
    assert ssd.scan_plan(1, 300, 4, 128, 2, 128, 256)["programs"] == 4
    assert ssd.scan_plan(1, 300, 8, 32, 2, 128, 128) == dict(
        kernel="pallas", chunk=128, heads_per_program=4, programs=6)


def test_models_state_their_plan_and_the_trainer_announces_it(tmp_path):
    from tpudist import telemetry
    from tpudist.models import create_model
    from tpudist.trainer import Trainer
    published = create_model("nemotron3_nano_30b_a3b", layers=9)
    assert published.scan_plan(2, 8192)["kernel"] == "pallas"
    tiny = create_model("nemotron3_tiny", layers=4)
    plan = tiny.scan_plan(16, 32)
    assert plan["kernel"] == "xla" and plan["heads_per_program"] == 4
    # a decoder without the mixer has no plan
    assert create_model("mellum2_tiny").scan_plan(16, 32) is None
    lines = []
    sink = telemetry.Telemetry(str(tmp_path), heartbeat=False)
    fake = types.SimpleNamespace(log=lines.append, telemetry=sink)
    for p in (published.scan_plan(2, 8192), plan, None):
        if p is not None:           # `MoEDecoder.plans` leaves it out
            Trainer._announce_plan(fake, "ssm_scan", p)
    sink.close()
    assert lines[0] == ("=> ssm_scan: pallas (chunk 128, heads_per_program 8, "
                        "programs 1024)")
    assert lines[1].startswith("=> ssm_scan: xla (chunk 8, heads_per_program "
                               "4, programs 128: a chunk of 8 is no")
    assert len(lines) == 2
    with open(telemetry.events_path(str(tmp_path), 0)) as f:
        events = [e for e in map(json.loads, f) if e["type"] == "ssm_scan"]
    assert [e["kernel"] for e in events] == ["pallas", "xla"]
    assert set(telemetry.SCHEMA["ssm_scan"]) <= set(events[0])
    assert "reason" in events[1] and "reason" not in events[0]
