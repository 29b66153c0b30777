"""Losses (reference criterion: ``nn.CrossEntropyLoss().cuda()``,
``distributed.py:147``)."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from tpudist.obs import scopes


class Scored(NamedTuple):
    """What a model that takes its loss itself hands the step in place of
    logits (a language model given ``targets``: its logits are never
    whole). ``cross_entropy_loss`` and ``ops.accuracy`` read it as they
    read logits; ``counters`` ride the step's metrics to the drain."""
    loss: jax.Array            # float32: the mean over every position, or
                               # what a weighted objective sums (lm_head_loss)
    acc1: jax.Array            # top-1 of the target, percent
    counters: dict             # name -> scalar, a step


def cross_entropy_loss(logits: jax.Array | Scored, targets: jax.Array,
                       label_smoothing: float = 0.0) -> jax.Array:
    """Mean softmax cross-entropy over integer labels: ``logits``
    [..., classes] against ``targets`` [...] (a batch of labels, or
    [rows, T] next ids under [rows, T, vocabulary] logits), the mean over
    every leading position. A ``Scored`` is its own loss.

    Matches ``nn.CrossEntropyLoss`` (log-softmax + NLL, mean reduction,
    ``distributed.py:147,247``). Computed in float32 regardless of the compute
    dtype so the loss/grad scale is stable under the bf16 policy (the
    GradScaler-free TPU answer to ``distributed_syncBN_amp.py:275-278``).
    """
    if isinstance(logits, Scored):
        if label_smoothing:
            raise ValueError("a model that takes its own loss does not "
                             "smooth its labels")
        return logits.loss
    logits = logits.astype(jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    n_classes = logits.shape[-1]
    if label_smoothing > 0.0:
        onehot = jax.nn.one_hot(targets, n_classes, dtype=jnp.float32)
        onehot = onehot * (1.0 - label_smoothing) + label_smoothing / n_classes
        nll = -(onehot * log_probs).sum(axis=-1)
    else:
        nll = -jnp.take_along_axis(log_probs, targets[..., None],
                                   axis=-1)[..., 0]
    return nll.mean()


def lm_head_loss(hidden: jax.Array, kernel: jax.Array, targets: jax.Array,
                 chunk: int = 2048, weights: jax.Array | None = None,
                 normaliser: float | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Output head and cross entropy of a language model, taken ``chunk``
    positions at a time so that the logits are never whole
    (``[16384, 24576]`` float32 would be 1.6 GB, twice with the cotangent):
    ``hidden`` [rows, T, d] x ``kernel`` [d, V] against ``targets``
    [rows, T]. Each chunk's logits are made again in the backward pass
    (``jax.checkpoint``), products in ``hidden``'s dtype accumulated in
    float32, the loss in float32.

    Without ``weights``: (mean loss over all rows x T positions, top-1
    accuracy in percent over them). With ``weights`` [rows, T] float32 (a
    diffusion objective's ``masked / t``): the sum of ``weight x cross
    entropy`` over ``normaliser`` (rows x T where not given), and the
    accuracy over the positions whose weight is not zero (0 where none)."""
    rows, t, d = hidden.shape
    n = rows * t
    # the largest chunk up to the asked size that divides the positions
    chunk = next(c for c in range(min(chunk, n), 0, -1) if n % c == 0)
    w = kernel.astype(hidden.dtype)

    @jax.checkpoint
    def one(carry, xs):
        h, y, *weight = xs
        with jax.named_scope(scopes.LM_HEAD):
            logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
        with jax.named_scope(scopes.LOSS):
            nll = (jax.nn.logsumexp(logits, axis=-1)
                   - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
            if weight:
                nll = nll * weight[0]
        with jax.named_scope(scopes.METRICS):
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
