"""Ambient-mesh helper for the trace-time kernel wrapper
(``flash_attention_spmd``)."""

from __future__ import annotations

import jax


def ambient_auto_axes(axes=("data", "model")):
    """``(mesh, auto)``: the ambient abstract mesh usable for a nested
    manual ``shard_map`` and the subset of ``axes`` that are
    partitioner-managed (Auto) in it — i.e. the axes a trace-time kernel
    wrapper may claim. Returns ``(None, frozenset())`` when there is no
    ambient mesh (eager, plain jit); inside a shard_map body (the
    DP/SP/EP/PP step paths) the bound axes read as Manual, so callers
    degrade to the plain kernel exactly where wrapping would be wrong."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return None, frozenset()
    auto = frozenset(
        a for a, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Auto and a in axes)
    return am, auto
