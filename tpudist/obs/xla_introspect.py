"""Post-compile introspection of a jitted step: where the FLOPs, bytes, and
HBM go *inside* the compiled program.

PR 2's telemetry can say a step took 300 ms; it cannot say whether that is
matmul FLOPs, an all-reduce that grew with the mesh, or an HBM spike from
XLA temp buffers. This module answers that from the three compiler surfaces
every ``lower().compile()`` executable already carries (no extra compile, no
runtime cost):

- ``cost_analysis()``  — program FLOPs / bytes-accessed / transcendentals
  (the same unwrap path ``tests/test_compiled_cost.py`` goldens);
- ``memory_analysis()`` — buffer-assignment breakdown: argument / output /
  temp (scratch) / generated-code bytes, minus donated aliases — the
  compiler-side HBM budget, attributing a spike to temps vs weights;
- the optimized HLO text — an **op census**: counts per op kind and a
  **collective census** (all-reduce / all-gather / reduce-scatter /
  all-to-all / collective-permute) with payload bytes per step, so comms
  growth is attributed, not just observed.

Everything is best-effort per section (a backend may expose any subset) and
returns plain JSON-serializable scalars, because the result is surfaced in
three places: the ``compile`` telemetry event (``event_fields``), the
``summarize`` report, and bench rows.
"""

from __future__ import annotations

import re
from typing import Callable, Optional

# dtype prefix → bytes/element for HLO shape strings like f32[64,128]{1,0}
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                   "collective-permute")

# `%name = <shapes> op-name(` — group 1: result shape(s) (possibly a tuple),
# group 2: the op kind. The shape class must admit TPU layout annotations —
# tiling `{1,0:T(8,128)}`, memory space `{1,0:S(1)}`, dynamic bounds
# `[<=8]` — or tiled instructions silently vanish from the census on the
# exact platform it targets. The op name is anchored as a LOWERCASE word
# after whitespace, which layout tokens (`T(`, `S(`) never satisfy.
# `-start` variants (async collectives) are folded into their base op;
# `-done` carries no payload and is skipped.
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*([\w\[\](){}<=:,.\s/#*-]*?)\s+"
    r"([a-z][\w\-]*)\(")
# Dims admit bounded-dynamic sizes ("<=8" — counted at their upper bound).
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,<=]*)\]")


def shape_bytes(shape_str: str, largest_only: bool = False) -> int:
    """Bytes of the array shape(s) in an HLO result-type string (unknown
    dtypes count 0). Tuples SUM their elements by default (a variadic sync
    all-reduce's tuple is N real payloads); ``largest_only`` takes the
    single largest array instead — async ``-start`` ops return tuples that
    alias the INPUT next to the output (plus u32 context scalars), where
    summing would double-count the transfer."""
    sizes = []
    for dtype, dims in _SHAPE.findall(shape_str):
        unit = _DTYPE_BYTES.get(dtype)
        if unit is None:
            continue
        n = 1
        for d in dims.split(","):
            d = d.replace("<=", "")
            if d:
                n *= int(d)
        sizes.append(n * unit)
    if not sizes:
        return 0
    return max(sizes) if largest_only else sum(sizes)


# Per-line replica-group parses, for the link-traffic estimate: the literal
# form `replica_groups={{0,1,2,3},{4,5,6,7}}` (group size = first group's
# member count) and the iota form `replica_groups=[4,2]<=[8]` (4 groups of
# 2 — group size is the SECOND dimension).
_GROUPS_LITERAL = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[\d+,(\d+)\]")


def _group_size(line: str) -> Optional[int]:
    m = _GROUPS_LITERAL.search(line)
    if m:
        return m.group(1).count(",") + 1
    m = _GROUPS_IOTA.search(line)
    if m:
        return int(m.group(1))
    return None


def _link_bytes(base: str, payload: int, g: Optional[int]) -> int:
    """Estimated wire traffic of one collective from its census payload
    (= result bytes) and group size ``g``, using the standard ring costs:
    all-reduce moves 2(g−1)/g × its buffer, all-gather/all-to-all
    (g−1)/g × the gathered/exchanged buffer, reduce-scatter (g−1) × its
    (1/g-sized) result, a permute exactly its payload. The payload metric
    under-credits RS/AG decompositions (an all-reduce counts its full f32
    result once; the equivalent RS+AG pair counts ~1.25×n for the same
    wire work), so comms-shrinking rewrites are judged on THIS number —
    with no parseable group, the asymptotic factor stands in (documented
    estimate, not a measurement)."""
    if g is not None and g < 2:
        return 0
    if base == "all-reduce":
        return int(payload * (2 * (g - 1) / g if g else 2.0))
    if base == "reduce-scatter":
        return int(payload * (g - 1)) if g else payload
    if base in ("all-gather", "all-to-all"):
        return int(payload * ((g - 1) / g if g else 1.0))
    return payload                        # collective-permute


def hlo_op_census(hlo_text: str) -> dict:
    """Counts per op kind + collective payload bytes (+ estimated link
    traffic) from optimized HLO."""
    op_counts: dict[str, int] = {}
    collectives: dict[str, dict] = {}
    link_bytes: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        shapes, op = m.group(1), m.group(2)
        if op.endswith("-done"):
            continue                      # async pair: -start carried payload
        base = op[:-6] if op.endswith("-start") else op
        op_counts[base] = op_counts.get(base, 0) + 1
        if base in _COLLECTIVE_OPS:
            c = collectives.setdefault(base, {"count": 0, "bytes": 0})
            c["count"] += 1
            payload = shape_bytes(shapes,
                                  largest_only=op.endswith("-start"))
            c["bytes"] += payload
            link_bytes[base] = link_bytes.get(base, 0) + _link_bytes(
                base, payload, _group_size(line))
    return {"op_counts": op_counts, "collectives": collectives,
            "link_bytes": link_bytes}


# HLO op kind → coarse execution-unit category, for the summarize
# time-attribution table (an MFU figure alone names nothing about where the
# rest of the time goes). Categories are chosen by which hardware
# resource the op *occupies*: MXU (systolic matmuls), VPU elementwise,
# reductions, pure data movement (layout/copy — zero arithmetic, pure
# HBM/VMEM traffic), collectives (ICI/DCN), and control/bookkeeping ops
# that cost nothing at runtime. Ops not listed fall into "other"
# (fusion wrappers excluded: their BODIES are censused line-by-line too,
# counting the wrapper would double-book every fused op).
_OP_CATEGORY = {}
for _op in ("dot", "convolution", "dot-general"):
    _OP_CATEGORY[_op] = "mxu"
for _op in ("add", "subtract", "multiply", "divide", "maximum", "minimum",
            "exponential", "log", "rsqrt", "sqrt", "power", "tanh",
            "logistic", "negate", "abs", "sign", "floor", "ceil",
            "round-nearest-afz", "compare", "select", "and", "or", "not",
            "xor", "clamp", "convert", "exponential-minus-one", "cosine",
            "sine", "is-finite", "remainder", "shift-left",
            "shift-right-logical", "shift-right-arithmetic", "atan2",
            "cbrt", "erf", "popcnt", "stochastic-convert"):
    _OP_CATEGORY[_op] = "vpu"
for _op in ("reduce", "reduce-window", "select-and-scatter", "sort",
            "reduce-precision"):
    _OP_CATEGORY[_op] = "reduce"
for _op in ("copy", "copy-start", "transpose", "reshape", "bitcast",
            "bitcast-convert", "broadcast", "slice", "dynamic-slice",
            "dynamic-update-slice", "concatenate", "pad", "gather",
            "scatter", "iota", "reverse"):
    _OP_CATEGORY[_op] = "copy"
for _op in _COLLECTIVE_OPS:
    _OP_CATEGORY[_op] = "collective"
for _op in ("parameter", "constant", "tuple", "get-tuple-element", "while",
            "conditional", "call", "after-all", "partition-id", "replica-id",
            "rng-bit-generator", "rng-get-and-update-state", "domain",
            "opt-barrier"):
    _OP_CATEGORY[_op] = "control"

OP_CATEGORIES = ("mxu", "vpu", "reduce", "copy", "collective", "control",
                 "other")


def op_category_counts(op_counts: dict) -> dict:
    """Roll the per-kind census up into execution-unit categories. Fusion
    wrappers are skipped (their bodies are already counted); custom-call is
    "other" (on TPU it is usually an opaque Mosaic/Pallas kernel)."""
    out = {c: 0 for c in OP_CATEGORIES}
    for op, n in op_counts.items():
        if op == "fusion":
            continue
        out[_OP_CATEGORY.get(op, "other")] += n
    return out


def memory_breakdown(compiled) -> dict:
    """``memory_analysis()``'s buffer-assignment numbers plus the one
    compiler-side HBM formula (args + outputs + temps + code − aliased) —
    the single definition of "compiled HBM" behind ``introspect`` (and
    thereby bench rows' ``hbm_compiled_gb`` and the compile event). Raises
    when the backend has no memory analysis; callers own the policy."""
    ma = compiled.memory_analysis()
    out = {"arg_bytes": int(ma.argument_size_in_bytes),
           "out_bytes": int(ma.output_size_in_bytes),
           "temp_bytes": int(ma.temp_size_in_bytes),
           "gen_code_bytes": int(ma.generated_code_size_in_bytes),
           "alias_bytes": int(ma.alias_size_in_bytes)}
    out["hbm_compiled_bytes"] = (out["arg_bytes"] + out["out_bytes"]
                                 + out["temp_bytes"] + out["gen_code_bytes"]
                                 - out["alias_bytes"])
    return out


def introspect(compiled, log: Optional[Callable[[str], None]] = None) -> dict:
    """Every number the three compiler surfaces give up, as flat scalars
    (plus the nested censuses). Missing surfaces simply leave their keys
    absent — callers treat the dict as sparse."""
    from tpudist.telemetry import cost_analysis_dict
    out: dict = {}

    def note(msg: str) -> None:
        if log is not None:
            try:
                log(msg)
            except Exception:
                pass

    try:
        cost = cost_analysis_dict(compiled)
        for key, name in (("flops", "flops"),
                          ("bytes accessed", "bytes_accessed"),
                          ("transcendentals", "transcendentals")):
            if cost.get(key):
                out[name] = float(cost[key])
        # Per-operand/output byte attribution when the backend provides it
        # (keys like "bytes accessed output" / "bytes accessed operand 0 {}").
        opd = {k: float(v) for k, v in cost.items()
               if k.startswith("bytes accessed ") and v}
        if opd:
            out["bytes_accessed_detail"] = opd
    except Exception as e:
        note(f"cost_analysis unavailable: {e!r}")

    try:
        out.update(memory_breakdown(compiled))
    except Exception as e:
        note(f"memory_analysis unavailable: {e!r}")

    try:
        census = hlo_op_census(compiled.as_text())
        out["op_counts"] = census["op_counts"]
        out["collectives"] = census["collectives"]
        out["collective_ops"] = sum(c["count"]
                                    for c in census["collectives"].values())
        out["collective_bytes_per_step"] = sum(
            c["bytes"] for c in census["collectives"].values())
        if census["collectives"]:
            out["collective_link_bytes"] = sum(
                census["link_bytes"].values())
    except Exception as e:
        note(f"HLO census unavailable: {e!r}")
    return out


# Flat numeric fields safe to ride on a telemetry ``compile`` event / bench
# row (the nested censuses stay out of the hot event stream; summarize
# re-derives what it needs from these).
EVENT_FIELDS = ("flops", "bytes_accessed", "transcendentals", "arg_bytes",
                "out_bytes", "temp_bytes", "gen_code_bytes", "alias_bytes",
                "hbm_compiled_bytes", "collective_ops",
                "collective_bytes_per_step", "collective_link_bytes") \
    + tuple(f"ops_{c}" for c in OP_CATEGORIES)


def event_fields(info: dict) -> dict:
    """The flat-scalar subset of ``introspect``'s result, for emitting on
    the ``compile`` telemetry event and stamping into bench rows."""
    out = {k: info[k] for k in EVENT_FIELDS
           if isinstance(info.get(k), (int, float))}
    # Op-category rollup as flat numeric fields: the compile event (and
    # bench rows) carry ops_mxu/ops_vpu/... so summarize can print the
    # time-attribution table without the full per-kind census.
    if info.get("op_counts"):
        for c, n in op_category_counts(info["op_counts"]).items():
            out[f"ops_{c}"] = n
    # Headline comms number: all-reduce count (the data-parallel gradient
    # sync — the op whose growth tracks mesh size).
    ar = (info.get("collectives") or {}).get("all-reduce")
    if ar:
        out["all_reduce_count"] = ar["count"]
        out["all_reduce_bytes"] = ar["bytes"]
    return out


def format_section(info: dict) -> list[str]:
    """Human lines for the summarize report (empty when nothing is known)."""
    L: list[str] = []
    if not info:
        return L
    gb = 2.0 ** 30
    if info.get("flops"):
        line = f"    flops/step {info['flops']:.3e}"
        if info.get("bytes_accessed"):
            line += (f", bytes accessed {info['bytes_accessed']:.3e} "
                     f"(arith intensity "
                     f"{info['flops'] / info['bytes_accessed']:.1f} "
                     f"flop/byte)")
        L.append(line)
    if info.get("hbm_compiled_bytes"):
        parts = [f"{name} {info[k] / gb:.3f}"
                 for name, k in (("args", "arg_bytes"), ("out", "out_bytes"),
                                 ("temps", "temp_bytes"),
                                 ("code", "gen_code_bytes"))
                 if info.get(k) is not None]
        alias = info.get("alias_bytes") or 0
        L.append(f"    HBM (compiler view) "
                 f"{info['hbm_compiled_bytes'] / gb:.3f} GB  "
                 f"[{', '.join(parts)}"
                 + (f", -aliased {alias / gb:.3f}" if alias else "") + "]")
    colls = info.get("collectives") or {}
    if colls:
        per = ", ".join(f"{op} x{c['count']} ({c['bytes'] / 2**20:.1f} MiB)"
                        for op, c in sorted(colls.items()))
        L.append(f"    collectives/step: {per}")
    elif info.get("collective_ops"):
        # Flat-field consumers (summarize reads the compile event, which
        # carries no per-op census beyond all-reduce): a reduce-scatter /
        # all-gather program must still show its comms total.
        L.append(f"    collectives/step: {info['collective_ops']:.0f} ops "
                 f"({(info.get('collective_bytes_per_step') or 0) / 2**20:.1f}"
                 f" MiB)")
    elif "collective_ops" in info or info.get("op_counts"):
        L.append("    collectives/step: none (single-device program)")
    return L
