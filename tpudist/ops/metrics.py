"""Classification metrics (reference ``utils.py:105-111``).

The reference computes top-k accuracy with ``scores.topk`` → eq with expanded
targets → fraction correct, and deliberately returns a 0-D tensor (not a float)
so it stays allreduce-able. Same here: these are jnp functions that fold into
the jitted step and stay on device, so the cross-replica ``pmean`` fuses in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpudist.obs import scopes


def accuracy(scores, targets: jax.Array, topk: int = 1) -> jax.Array:
    """Fraction (in %) of rows whose true label is within the top-k scores
    (of positions, under [rows, T, vocabulary] scores and top-1). A
    ``Scored`` (a model that took its loss itself) carries its own top-1.

    Matches reference ``accuracy`` with ``topk=(1,)`` (``utils.py:105-111``):
    returns a 0-D array scaled to percent (mul_(100.0 / batch_size)).
    """
    from tpudist.ops.loss import Scored
    if isinstance(scores, Scored):
        if topk != 1:
            raise ValueError("a model that takes its own loss counts top-1")
        return scores.acc1
    with jax.named_scope(scopes.METRICS):     # label only; every step inherits
        if topk == 1:
            pred = jnp.argmax(scores, axis=-1)
            correct = (pred == targets).sum()
        else:
            _, pred = jax.lax.top_k(scores, topk)          # [B, k]
            correct = (pred == targets[:, None]).any(axis=-1).sum()
        return correct.astype(jnp.float32) * (100.0 / targets.size)
