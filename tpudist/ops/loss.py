"""Losses (reference criterion: ``nn.CrossEntropyLoss().cuda()``,
``distributed.py:147``)."""

from __future__ import annotations

import functools
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


def head_chunk(positions: int, chunk: int) -> int:
    """The head loss's chunk rule: the largest chunk up to the asked size
    that divides the positions."""
    return next(c for c in range(min(chunk, positions), 0, -1)
                if positions % c == 0)


def _chunks_scan(hidden, kernel, targets, weights, chunk: int, norm: float,
                 grads: bool):
    """The head's one loop over chunks of ``chunk`` positions: (loss over
    ``norm``, accuracy) and, with ``grads``, the loss's gradients at a
    cotangent of one, taken while a chunk's logits are there: d hidden
    (``hidden``'s shape and dtype), d kernel (float32, the loop's carry) and
    each position's unweighted cross entropy (``weights``' gradient times
    ``norm``; None without weights). Without ``grads`` a chunk is
    rematerialised (``jax.checkpoint``) when the loop is differentiated."""
    d = hidden.shape[-1]
    n = hidden.size // d
    w = kernel.astype(hidden.dtype)

    def one(carry, xs):
        h, y, *weight = xs
        with jax.named_scope(scopes.LM_HEAD):
            logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
        with jax.named_scope(scopes.LOSS):
            lse = jax.nn.logsumexp(logits, axis=-1)
            nll = lse - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            scored = nll * weight[0] if weight else nll
        with jax.named_scope(scopes.METRICS):
            hit = jnp.argmax(logits, axis=-1) == y
            if weight:
                hit &= weight[0] != 0
            hits = jnp.sum(hit)
        sums = (carry[0] + jnp.sum(scored), carry[1] + hits)
        if not grads:
            return sums, None
        with jax.named_scope(scopes.LOSS):
            # d (sum of scored / norm) / d logits: the softmax less the
            # target's one, a position's weight over the normaliser; in the
            # compute dtype for its two products, as a transposed dot's
            # operand is at default precision
            ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            dlogits = jnp.exp(logits - lse[:, None]) - (ids == y[:, None])
            per = weight[0][:, None] / norm if weight else 1.0 / norm
            dlogits = (dlogits * per).astype(h.dtype)
        with jax.named_scope(scopes.LM_HEAD):
            dh = jax.lax.dot_general(
                dlogits, w, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(h.dtype)
            # a fresh carry a turn: a loop that updates one in place copies
            # it whole (PR 31)
            dw = carry[2] + jax.lax.dot_general(
                h, dlogits, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return sums + (dw,), (dh, nll if weight else None)

    xs = (hidden.reshape(n // chunk, chunk, d),
          targets.reshape(n // chunk, chunk))
    if weights is not None:
        xs += (weights.astype(jnp.float32).reshape(n // chunk, chunk),)
    zeros = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    if grads:
        (total, hits, dw), (dh, nll) = jax.lax.scan(
            one, zeros + (jnp.zeros(kernel.shape, jnp.float32),), xs)
    else:
        (total, hits), _ = jax.lax.scan(jax.checkpoint(one), zeros, xs)
    if weights is None:
        out = total / norm, hits.astype(jnp.float32) * (100.0 / n)
    else:
        scored = jnp.maximum(jnp.sum(weights != 0), 1).astype(jnp.float32)
        out = total / norm, hits.astype(jnp.float32) * 100.0 / scored
    if not grads:
        return out
    return out + (dh.reshape(hidden.shape), dw,
                  None if weights is None else nll.reshape(weights.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grads_in_forward_loop(hidden, kernel, targets, weights, chunk, norm):
    return _chunks_scan(hidden, kernel, targets, weights, chunk, norm, False)


def _forward(hidden, kernel, targets, weights, chunk, norm):
    loss, acc1, *grads = _chunks_scan(hidden, kernel, targets, weights,
                                      chunk, norm, True)
    return (loss, acc1), grads


def _backward(chunk, norm, grads, cotangents):
    dh, dw, nll = grads
    g = cotangents[0]                   # the accuracy is a count's: flat
    with jax.named_scope(scopes.LOSS):
        return ((g * dh).astype(dh.dtype), g * dw, None,
                None if nll is None else g * nll / norm)


_grads_in_forward_loop.defvjp(_forward, _backward)


def lm_head_loss(hidden: jax.Array, kernel: jax.Array, targets: jax.Array,
                 chunk: int = 2048, weights: jax.Array | None = None,
                 normaliser: float | None = None,
                 rematerialised: bool = False
                 ) -> tuple[jax.Array, jax.Array]:
    """Output head and cross entropy of a language model, taken ``chunk``
    positions at a time so that the logits are never whole
    (``[16384, 24576]`` float32 would be 1.6 GB, twice with the cotangent):
    ``hidden`` [rows, T, d] x ``kernel`` [d, V] against ``targets``
    [rows, T]; products in ``hidden``'s dtype accumulated in float32, the
    loss in float32.

    Without ``weights``: (mean loss over all rows x T positions, top-1
    accuracy in percent over them). With ``weights`` [rows, T] float32 (a
    diffusion objective's ``masked / t``): the sum of ``weight x cross
    entropy`` over ``normaliser`` (rows x T where not given), and the
    accuracy over the positions whose weight is not zero (0 where none).

    **Two forms of one loop** (``_chunks_scan``), for needs that conflict.
    The result is a scalar, so every gradient is linear in its one
    cotangent ``g``: the forward loop takes them at ``g = 1`` while a
    chunk's logits are there (three products a chunk: the logits, d hidden,
    d kernel) and the backward pass multiplies by ``g``
    (``jax.custom_vjp``). Its residuals are what the backward pass would
    make at the same point of the step: d hidden [rows, T, d] in
    ``hidden``'s dtype (in place of the saved ``hidden``: 75 MB at 16,384
    positions of 2,304), d kernel [d, V] float32 (226 MB at 2,304 x 24,576;
    it lives until the optimizer anyway) and, with ``weights``, each
    position's cross entropy [rows, T] float32 (64 KiB at 16,384: the
    weights' gradient). ``rematerialised`` keeps each chunk's logits for
    neither pass and makes them again in the backward loop
    (``jax.checkpoint``: four products a chunk in two loops): for a caller
    under a scan, which would stack d kernel once a turn (the looped
    decoder's passes, ``models/decoder.py::_looped``: the function cannot see
    the scan around it)."""
    n = hidden.size // hidden.shape[-1]
    form = (functools.partial(_chunks_scan, grads=False) if rematerialised
            else _grads_in_forward_loop)
    return form(hidden, kernel, targets, weights, head_chunk(n, chunk),
                n if weights is None else normaliser or n)
