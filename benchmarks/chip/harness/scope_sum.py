"""Device time a step under scopes of the program's own naming, for readers
that sum several (`metrics/moe_ms.py`, `metrics/lm_head_ms.py`).

`harness/roofline.py::under` matches a bare path element; a scope that a
transformation wrapped (`jvp(tpudist_loss)`, `transpose(jvp(...))`) matches
here too.
"""

from __future__ import annotations


def under(op_name: str | None, scope: str) -> bool:
    """`scope` is, or is wrapped in, a path element of `op_name`."""
    return any(scope == part or f"({scope})" in part
               for part in (op_name or "").replace(";", "/").split("/"))


def scope_ms(scopes: dict, names) -> dict:
    """{name: ms a step in named operations under it} over `step_scopes`'
    rows; an operation under two of them counts for the first, an unnamed
    instruction the compiler added (a layout copy) for none."""
    out = dict.fromkeys(names, 0.0)
    for _, ms, bucket, op_name in scopes["ops"]:
        if bucket == "layout_copy":
            continue
        hit = next((n for n in names if under(op_name, n)), None)
        if hit is not None:
            out[hit] += ms
    return out
