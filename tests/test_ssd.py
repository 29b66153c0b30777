"""The Mamba-2 mixer's pieces (``tpudist/ops/ssd.py``) against their plain
statements: the chunked scan against the recurrence one position at a time
(outputs and every gradient), the convolution against a shifted sum, the
gated group norm against its formula."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpudist.ops import ssd

HEADS, P, GROUPS, N, CHUNK = 4, 8, 2, 16, 8


def recurrence(x, dt, a_log, b, c, d):
    """``h_t = exp(dt_t A) h_(t-1) + dt_t x_t B_t^T``, ``y_t = h_t C_t + D
    x_t``, a position at a time, float32."""
    a = -jnp.exp(a_log)
    rep = x.shape[2] // b.shape[2]
    b, c = jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2)

    def one(h, step):
        x_t, dt_t, b_t, c_t = step                    # [B, H, ...]
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t) + d[:, None] * x_t
    h0 = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[3]))
    _, y = lax.scan(one, h0, tuple(jnp.moveaxis(v, 1, 0)
                                   for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(t, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(keys[0], (2, t, HEADS, P)),
        dt=jax.nn.softplus(jax.random.normal(keys[1], (2, t, HEADS)) - 2.0),
        a_log=jnp.log(jnp.arange(1, HEADS + 1, dtype=jnp.float32)),
        b=jax.random.normal(keys[2], (2, t, GROUPS, N)),
        c=jax.random.normal(keys[3], (2, t, GROUPS, N)),
        d=jax.random.normal(keys[4], (HEADS,)),
        w=jax.random.normal(keys[5], (2, t, HEADS, P)))


def _chunked(x, dt, a_log, b, c, d):
    return ssd.ssd_scan(x, dt, -jnp.exp(a_log), b, c, d, CHUNK)[0]


# a multiple of the chunk, one that is not, one shorter than a chunk
@pytest.mark.parametrize("t", [24, 19, 5])
def test_chunked_scan_is_the_recurrence(t):
    v = _inputs(t)
    w = v.pop("w")
    with jax.default_matmul_precision("highest"):
        want = recurrence(**v)
        got = _chunked(**v)
    assert got.shape == want.shape == (2, t, HEADS, P)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [24, 19, 5])
def test_chunked_scan_gradients_are_the_recurrences(t):
    v = _inputs(t, seed=1)
    w = v.pop("w")
    names = tuple(v)

    def loss(f):
        return lambda *args: jnp.sum(f(*args) * w)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(recurrence), argnums=range(6))(*v.values())
        got = jax.grad(loss(_chunked), argnums=range(6))(*v.values())
    for name, g, r in zip(names, got, want):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4 * scale,
                                   err_msg=name)


def test_carry_min_is_the_fastest_fading_heads_chunk():
    v = _inputs(24, seed=2)
    _, carry_min = ssd.ssd_scan(v["x"], v["dt"], -jnp.exp(v["a_log"]),
                                v["b"], v["c"], v["d"], CHUNK)
    per_chunk = jnp.exp(jnp.sum(
        (v["dt"] * -jnp.exp(v["a_log"])).reshape(2, 3, CHUNK, HEADS), axis=2))
    np.testing.assert_allclose(carry_min, jnp.min(per_chunk), rtol=1e-5)
    # padding neither decays nor writes the state: dt = 0 there
    _, padded = ssd.ssd_scan(v["x"][:, :19], v["dt"][:, :19],
                             -jnp.exp(v["a_log"]), v["b"][:, :19],
                             v["c"][:, :19], v["d"], CHUNK)
    assert float(padded) >= float(carry_min)


def test_scan_takes_bfloat16_products_and_float32_decays():
    v = _inputs(24, seed=3)
    low = {k: v[k].astype(jnp.bfloat16) for k in ("x", "b", "c")}
    got, _ = ssd.ssd_scan(low["x"], v["dt"], -jnp.exp(v["a_log"]), low["b"],
                          low["c"], v["d"], CHUNK)
    assert got.dtype == jnp.float32
    want = recurrence(low["x"].astype(jnp.float32), v["dt"], v["a_log"],
                      low["b"].astype(jnp.float32),
                      low["c"].astype(jnp.float32), v["d"])
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 0.01, err


def test_causal_conv_is_a_shifted_sum():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (2, 11, 6))
    kernel = jax.random.normal(jax.random.fold_in(key, 1), (4, 6))
    bias = jax.random.normal(jax.random.fold_in(key, 2), (6,))
    got = ssd.causal_conv1d(x, kernel, bias)
    want = np.zeros((2, 11, 6), np.float32) + np.asarray(bias)
    for t in range(11):
        for k in range(4):
            src = t - 3 + k
            if src >= 0:
                want[:, t] += np.asarray(kernel[k]) * np.asarray(x[:, src])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and torch's conv1d(padding=3)[..., :T] as XLA's grouped convolution
    conv = lax.conv_general_dilated(
        jnp.moveaxis(x, 1, 2), jnp.moveaxis(kernel, 0, 1)[:, None, :],
        (1,), [(3, 0)], feature_group_count=6)
    np.testing.assert_allclose(got, jnp.moveaxis(conv, 1, 2) + bias,
                               rtol=1e-5, atol=1e-5)
    # causal: a later position moves no earlier output
    moved = ssd.causal_conv1d(x.at[:, 7].add(1.0), kernel, bias)
    np.testing.assert_array_equal(moved[:, :7], got[:, :7])


def test_gated_group_norm_gates_first_then_norms_each_group():
    key = jax.random.PRNGKey(5)
    y = jax.random.normal(key, (3, 12))
    z = jax.random.normal(jax.random.fold_in(key, 1), (3, 12))
    w = jax.random.normal(jax.random.fold_in(key, 2), (12,))
    got = ssd.gated_group_norm(y, z, w, groups=3, eps=1e-5)
    g = np.asarray(y * jax.nn.silu(z)).reshape(3, 3, 4)
    want = (g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 12) * np.asarray(w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # not the other order (norm, then gate)
    other = (np.asarray(y).reshape(3, 3, 4) / np.sqrt(
        (np.asarray(y).reshape(3, 3, 4) ** 2).mean(-1, keepdims=True) + 1e-5)
    ).reshape(3, 12) * np.asarray(jax.nn.silu(z)) * np.asarray(w)
    assert np.abs(np.asarray(got) - other).max() > 0.1
