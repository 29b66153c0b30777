"""The head's loss before and after it took its gradients in its forward
loop (PR 46, `ops/loss.py::lm_head_loss`): the function as it stood, kept
here operation for operation, against both of its forms and inside every
decoder family's small model. (`tests/test_decoder.py` holds both forms to
the unchunked float32 cross entropy and counts the compiled loops.)

CPU, toy widths, seeded random weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _head_loss_before(hidden, kernel, targets, chunk=2048, weights=None,
                      normaliser=None, rematerialised=False):
    """`ops.lm_head_loss` as it stood before PR 46, operation for operation:
    every chunk rematerialised, whoever calls."""
    rows, t, d = hidden.shape
    n = rows * t
    chunk = next(c for c in range(min(chunk, n), 0, -1) if n % c == 0)
    w = kernel.astype(hidden.dtype)

    @jax.checkpoint
    def one(carry, xs):
        h, y, *weight = xs
        logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
        if weight:
            nll = nll * weight[0]
        hit = jnp.argmax(logits, axis=-1) == y
        if weight:
            hit &= weight[0] != 0
        hits = jnp.sum(hit)
        return (carry[0] + jnp.sum(nll), carry[1] + hits), None

    xs = (hidden.reshape(n // chunk, chunk, d),
          targets.reshape(n // chunk, chunk))
    if weights is not None:
        xs += (weights.astype(jnp.float32).reshape(n // chunk, chunk),)
    (total, hits), _ = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), xs)
    if weights is None:
        return total / n, hits.astype(jnp.float32) * (100.0 / n)
    scored = jnp.maximum(jnp.sum(weights != 0), 1).astype(jnp.float32)
    return (total / (normaliser or n),
            hits.astype(jnp.float32) * 100.0 / scored)


def _head_case():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    t = jax.random.uniform(ks[3], (2, 37), minval=0.1)
    return (jax.random.normal(ks[0], (2, 37, 16)),
            jax.random.normal(ks[1], (16, 50)),
            jax.random.randint(ks[2], (2, 37), 0, 50),
            jnp.where(t < 0.6, 1.0 / t, 0.0))


def tokens(t, rows=2, vocab=64, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, t + 1), 0,
                             vocab)
    return ids[:, :-1], ids[:, 1:]


def test_head_loss_is_to_the_bit_what_it_was():
    """Loss and accuracy of both forms are what the function computed before
    it took its gradients in the forward loop (the same operations on the
    same chunks), and weights of one are the plain mean."""
    from tpudist.ops import lm_head_loss
    hidden, kernel, targets, weights = _head_case()
    with jax.default_matmul_precision("highest"):
        for fields in ({}, dict(weights=weights),
                       dict(weights=weights, normaliser=148)):
            was = _head_loss_before(hidden, kernel, targets, 16, **fields)
            for form in (False, True):
                now = lm_head_loss(hidden, kernel, targets, chunk=16,
                                   rematerialised=form, **fields)
                assert [float(v) for v in now] == [float(v) for v in was]
        plain = lm_head_loss(hidden, kernel, targets, chunk=16)
        ones = lm_head_loss(hidden, kernel, targets, chunk=16,
                            weights=jnp.ones((2, 37)))
    assert abs(float(ones[0]) - float(plain[0])) < 1e-6
    assert abs(float(ones[1]) - float(plain[1])) < 1e-4


def _scored_step(arch, head_loss, monkeypatch):
    """(loss and every gradient leaf of ``arch``'s small configuration in
    bfloat16 at ``loss_chunk`` 64 with ``head_loss`` as the decoder's, the
    lowered program's text)."""
    from tpudist.models import create_model, decoder
    monkeypatch.setattr(decoder, "lm_head_loss", head_loss)
    model = create_model(arch, dtype=jnp.bfloat16, loss_chunk=64)
    x, y = tokens(128, vocab=64)
    variables = jax.jit(lambda k, x: model.init(k, x, train=False))(
        jax.random.PRNGKey(0), x)
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        out, _ = model.apply({"params": params, **rest}, x, train=True,
                             targets=y, mutable=["batch_stats"])
        return out.loss

    step = jax.jit(jax.value_and_grad(loss))
    return (step(variables["params"]),
            step.lower(variables["params"]).as_text())


@pytest.mark.parametrize("arch", [
    "mellum2_tiny", "sdar_tiny", "nemotron3_tiny", "joyai_tiny", "ouro_tiny"])
def test_a_decoder_step_is_what_it_was_before_the_head_took_its_gradients(
        arch, monkeypatch):
    """Every family's small model (two chunks of 64 a head call; sdar's
    weighted loss, joyai's second call through the MTP module with a
    normaliser): the loss is the parent's to the bit and every gradient leaf
    within bfloat16's rounding of it (d hidden is rounded where it was not:
    the leaves stand up to 2 % apart at these widths, and each side as far,
    15 % at most and 4 % in the mean, from the same model in float32). The
    looped model asks for the rematerialised form, and its lowered step is
    the parent's, text for text."""
    from tpudist.ops import lm_head_loss
    (loss, grads), text = _scored_step(arch, lm_head_loss, monkeypatch)
    (was, was_grads), was_text = _scored_step(arch, _head_loss_before,
                                              monkeypatch)
    assert float(loss) == float(was)
    if arch == "ouro_tiny":
        assert text == was_text
    else:
        assert text.count("stablehlo.while") < was_text.count(
            "stablehlo.while")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(was_grads)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 3e-2 * np.linalg.norm(b) + 1e-9, path


