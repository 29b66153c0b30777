"""A kernel's share of its roofline, from the device trace.

    share(ctx, metric, scope, calls) -> % or None

`scope` is the `jax.named_scope` the program put around the kernel's calls
(one path element of the operations' `op_name`, forward and transposed
alike); `calls` is what the kernel's calls of one step need by the
benchmark's own count, `[(flops, bytes), ...]`, one pair a kind of call
already multiplied by how many a step makes. The least time the chip could
take over a call is the larger of its operations over the peak rate and its
bytes over the HBM rate (`peaks.json`); the share is the sum of those over
the device seconds a step that `scope_reduce.step_scopes` finds in the
operations under the scope. Instructions the compiler added behind them with
no name of their own (layout copies) are not the kernel's and are printed
beside it, not counted.

A reader built on this returns None, and the metric is left out, where there
are no scopes or no operation lies under the scope: never 0. `cellrun`
refuses a `*_roofline` above 100 %: the operations or bytes are then counted
too high, or the scope leaves part of the work out.
"""

from __future__ import annotations

import json

from harness import scope_reduce


def under(op_name: str | None, scope: str) -> bool:
    return scope in (op_name or "").replace(";", "/").split("/")


def scope_ms(scopes: dict, scope: str) -> tuple[float, float, int]:
    """(ms a step in the operations under `scope`, ms a step in unnamed
    instructions added behind them, how many operations)."""
    own = behind = 0.0
    n = 0
    for _, ms, bucket, op_name in scopes["ops"]:
        if not under(op_name, scope):
            continue
        if bucket == "layout_copy":
            behind += ms
        else:
            own += ms
            n += 1
    return own, behind, n


def least_ms(calls, peak: dict) -> tuple[float, list[str]]:
    """The roofline's time for `calls`, and what bounds each."""
    total, bound_by = 0.0, []
    for flops, nbytes in calls:
        compute = 1e3 * flops / peak["flops_per_s_bf16"]
        memory = 1e3 * nbytes / peak["hbm_bytes_per_s"]
        total += max(compute, memory)
        bound_by.append("compute" if compute >= memory else "memory")
    return total, bound_by


def share(ctx: dict, metric: str, scope: str, calls, **said):
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    own, behind, n = scope_ms(scopes, scope)
    if not own:
        return None
    least, bound_by = least_ms(calls, ctx["peak"])
    value = 100.0 * least / own
    print("bench roofline " + json.dumps(dict(
        metric=metric, scope=scope, value_pct=value, least_ms=least,
        device_ms=own, operations=n, layout_copy_behind_ms=behind,
        bound_by=bound_by, calls=[list(c) for c in calls], **said)),
        flush=True)
    return value
