"""From a trace's device operations to the program's own names for them.

A TPU trace event carries its HLO line and three timing stats, no
`op_name`. The bridge is in the same `.xplane.pb`: the `/host:metadata` plane
holds one event-metadata entry per compiled module (named like the module
events, `jit_step(<id>)`) whose one bytes stat, `Hlo Proto`, is the optimized
`HloProto`, and its instructions carry `metadata.op_name`, the program's
scope path (`jit(step)/transpose(jvp(tpudist_forward))/ResNet/layer3_0/...`).
`jax.profiler.ProfileData` does not expose that plane's metadata, so
`hlo_scopes` reads it from the file's bytes with a protobuf wire reader: no
tensorflow or xprof import, in a process that already holds 14-19 GiB.

    hlo_scopes(xplane, module)   -> {"jit_step(<id>)": {
                                        "ops": {instruction: op_name},
                                        "members": {fusion: [op_name, ...]},
                                        "bare": {instruction: opcode}}}
    by_scope(events, scopes, ..) -> device ms a step by phase and by block
    step_scopes(ctx)             -> the two above for the run's newest trace,
                                    once a process; prints `bench scope_ms`
                                    (`ops`, every operation's row, is kept
                                    for `harness/roofline.py`, not printed)
    label_ops(device_ops, ...)   -> the result line's `breakdown.device_ops`
                                    with those names beside the bare ones

**What the phases are.** An operation goes to the phase of its own `op_name`,
and XLA gives a fusion its root's: `fwd` / `bwd` / `opt` are "time in fusions
rooted in", not "work of". Over half of a step sits in fusions whose members
span phases (a layer's weight update inside its weight-gradient fusion, the
forward's elementwise tail recomputed inside a backward fusion): that time is
also summed as `mixed_ms`, by the phases spanned. Instructions the compiler
added with no name at all (layout `copy.N`, `copy-start` / `copy-done`) are a
bucket of their own, `layout_copy`, never folded into a phase.

`step_scopes` gives None, and the readers under `metrics/` then leave their
metric out, where the trace has no `/host:metadata` plane, the step's names
hold no `tpudist_forward` (a program without the scopes), or more than 1 % of
a step lies under no scope (the program's coverage broke: fix the scope, the
split would mislead).
"""

from __future__ import annotations

import glob
import json
import os

from harness import trace_reduce

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
PHASES = ("fwd", "loss", "bwd", "reduce", "opt", "metrics")
# the three the metrics report: `mixed_ms` counts fusions that span two
GROUP = {"fwd": "fwd", "loss": "fwd", "bwd": "bwd",
         "reduce": "opt", "opt": "opt", "metrics": "opt"}
BUCKETS = PHASES + ("layout_copy", "unscoped")
UNSCOPED_LIMIT = 0.01        # of a step's busy time


# --- protobuf wire format ----------------------------------------------------
def _varint(buf, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of one message: an int for a varint or fixed
    field, a (start, end) pair of offsets for a length-delimited one."""
    pos = start
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire == 1:
            value, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif wire == 5:
            value, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield field, value


def _first(buf, span, field: int):
    return next((v for f, v in _fields(buf, *span) if f == field), None)


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace") if span else ""


def _map_values(buf, plane, field: int):
    """Values of a map<int64, Message> field (entry: key 1, value 2)."""
    for f, entry in _fields(buf, *plane):
        if f == field:
            value = _first(buf, entry, 2)
            if value is not None:
                yield value


# --- HloProto -> names -------------------------------------------------------
def _ids(buf, value) -> list[int]:
    """A repeated int64 field's value: packed, or one element."""
    if not isinstance(value, tuple):
        return [value]
    out, pos = [], value[0]
    while pos < value[1]:
        one, pos = _varint(buf, pos)
        out.append(one)
    return out


def _instruction(buf, span):
    name = opcode = op_name = ""
    uid, operands, called = None, [], []
    for f, v in _fields(buf, *span):
        if f == 1:
            name = _text(buf, v)
        elif f == 2:
            opcode = _text(buf, v)
        elif f == 7:
            op_name = _text(buf, _first(buf, v, 2))
        elif f == 35:
            uid = v
        elif f == 36:
            operands += _ids(buf, v)
        elif f == 38:
            called += _ids(buf, v)
    return name, opcode, op_name, uid, operands, called


def parse_hlo_proto(buf, span=None) -> dict:
    """{"ops": {instruction: op_name}, "members": {fusion: [op_name, ...]},
    "bare": {instruction: opcode}} of one serialized `HloProto`. A fusion
    keeps its own `op_name` (its root's, as XLA names it) and lists those of
    its fused computation's members (parameters, constants and tuple plumbing
    carry none). An instruction the compiler added with no metadata at all (a
    layout `copy`, an async `copy-done`) is listed under `bare` with its
    opcode; its `ops` entry is its first named operand's `op_name`: what it
    was added behind, not a name of its own."""
    buf = memoryview(buf)
    span = span or (0, len(buf))
    module = _first(buf, span, 1)
    ops, members, computations, calls, bare = {}, {}, {}, {}, {}
    for f, comp in _fields(buf, *module):
        if f != 3:
            continue
        cid, names, by_id = None, [], {}
        for cf, cv in _fields(buf, *comp):
            if cf == 5:
                cid = cv
            elif cf == 2:
                name, opcode, op_name, uid, operands, called = \
                    _instruction(buf, cv)
                if op_name:
                    names.append(op_name)
                elif opcode not in ("parameter", "constant"):
                    bare[name] = opcode
                    op_name = next((by_id[o] for o in operands
                                    if by_id.get(o)), "")
                ops[name] = by_id[uid] = op_name
                if opcode == "fusion":
                    calls[name] = called
        computations[cid] = names
    for name, called in calls.items():
        members[name] = [n for cid in called for n in computations.get(cid, [])]
    return {"ops": ops, "members": members, "bare": bare}


def hlo_scopes(xplane_path: str, module_name: str) -> dict | None:
    """{full module name: `parse_hlo_proto` of its `Hlo Proto`} for every
    module `module_name` of the trace file's `/host:metadata` plane (the
    trace appends the program's id in brackets: `jit_step(<id>)`; two step
    programs in one trace stay apart, because instruction names such as
    `fusion.60` repeat between them), or None where the file holds no such
    plane, module or `Hlo Proto`."""
    with open(xplane_path, "rb") as f:
        buf = memoryview(f.read())
    found = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1 or _text(buf, _first(buf, plane, 2)) != METADATA_PLANE:
            continue                  # every other plane is skipped whole
        stat_ids = {_first(buf, sm, 1) for sm in _map_values(buf, plane, 5)
                    if _text(buf, _first(buf, sm, 2)) == HLO_STAT}
        for em in _map_values(buf, plane, 4):
            name = _text(buf, _first(buf, em, 2))
            if name != module_name and not name.startswith(module_name + "("):
                continue
            for ef, stat in _fields(buf, *em):
                if ef == 5 and _first(buf, stat, 1) in stat_ids:
                    proto = _first(buf, stat, 6)
                    if proto and proto[1] > proto[0]:
                        found[name] = parse_hlo_proto(buf, proto)
    return found or None


# --- the benchmark's own copy of tpudist.obs.scopes.phase_of -----------------
def phase_of(op_name: str) -> str | None:
    """Five string tests; `selftest/test_scope_reduce.py` holds them to
    `tpudist.obs.scopes.phase_of` on every scope name."""
    for part in op_name.split(";"):
        if "tpudist_forward" in part or "tpudist_loss" in part:
            if "transpose(" in part:
                return "bwd"
            return "fwd" if "tpudist_forward" in part else "loss"
        if "tpudist_grad_reduce" in part:
            return "reduce"
        if "tpudist_optimizer" in part:
            return "opt"
        if "tpudist_metrics" in part:
            return "metrics"
    return None


def block_of(op_name: str, depth: int = 2) -> str | None:
    """The block of a forward or backward operation: the `depth` path
    elements after the model's name (`layer3_0/conv1`,
    `encoder_layer_3/mlp_0`), the trailing primitive left out; None outside
    the model."""
    for part in op_name.split(";"):
        path = part.split("/")
        at = next((i for i, el in enumerate(path) if "tpudist_forward" in el),
                  None)
        if at is not None:
            inner = path[at + 2:-1]       # after the model's name
            return "/".join(inner[:depth]) or "(top)"
    return None


def label_ops(device_ops: list, scopes: dict | None) -> list:
    """`trace_reduce.reduce`'s `device_ops` ([[bare name, seconds], ...])
    with each operation's bucket and block beside its name, as `step_scopes`
    ranks it: `fusion.87 [bwd encoder_layer_3/self_attention/attn_scores]`.
    An operation it does not rank keeps its bare name, and so does every
    one where there are no scopes (`scopes` None)."""
    ranked = {name: (bucket, op_name) for name, _, bucket, op_name
              in (scopes or {}).get("top_ops", [])}
    out = []
    for name, seconds in device_ops:
        if name in ranked:
            bucket, op_name = ranked[name]
            block = (block_of(op_name or "", depth=3)
                     if bucket in ("fwd", "bwd") else None)
            name = f"{name} [{bucket}{' ' + block if block else ''}]"
        out.append([name, seconds])
    return out


# --- device time by scope ----------------------------------------------------
def _self_times(rows):
    """(row, self nanoseconds) of rows [name, start, duration, ...] on one
    line: a row that contains later rows (a loop around its body) keeps only
    what they leave."""
    out, stack = [], []
    for row in sorted(rows, key=lambda r: (r[1], -r[2])):
        end = row[1] + row[2]
        while stack and stack[-1][0] <= row[1]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(end, stack[-1][0]) - row[1]
        stack.append((end, len(out)))
        out.append([row, row[2]])
    return out


def by_scope(events: dict, scopes: dict, step_module: str = "",
             window: tuple[int, int] | None = None) -> dict:
    """Device milliseconds a step, by phase and by block, over the whole
    steps of the traced window: the operations inside a module event named
    `step_module` that lies whole inside the window, as `device_step_ms`
    counts steps. `scopes` is `hlo_scopes`' result: each step's operations
    are named by the map of that step's own module (`jit_step(<id>)`).

    `phase_ms` splits the step's busy time into disjoint buckets. An
    operation goes to its own `op_name`'s phase, which for a fusion is its
    root's (a fusion whose own name has no scope, to its first scoped
    member's: `scoped_by_members_ms`); an instruction the compiler added
    with no name goes to `layout_copy` (`layout_copy_behind_ms` says behind
    which phase's operations they were added, `layout_copy_opcodes_ms` what
    they are); the rest is `unscoped`. Beside that split, `mixed_ms` sums the
    fusions whose members span two of forward / backward / optimizer, and
    `mixed_by_phases_ms` says which (XLA fuses a layer's weight update into
    its weight-gradient fusion: `bwd+opt`)."""
    host = events.get("host", [])
    if window is None:
        win = [r for r in host if r[0] == trace_reduce.WINDOW_SPAN]
        window = (win[0][1], win[0][1] + win[0][2]) if win else None
    phase_ns = dict.fromkeys(BUCKETS, 0)
    blocks, ops, steps, mixed, unnamed, by_members = {}, {}, 0, {}, 0, 0
    behind, opcodes, modules = {}, {}, set()
    nothing = {"ops": {}, "members": {}, "bare": {}}
    for dev in events.get("devices", []):
        if not dev["ops"]:
            continue
        lo, hi = window or (min(r[1] for r in dev["ops"]),
                            max(r[1] + r[2] for r in dev["ops"]))
        whole = sorted([r[1], r[1] + r[2], r[0]] for r in dev["modules"]
                       if (not step_module or r[0].startswith(step_module))
                       and r[1] >= lo and r[1] + r[2] <= hi)
        steps += len(whole)
        modules.update(w[2] for w in whole)
        at = 0
        for row, self_ns in _self_times(dev["ops"]):
            while at < len(whole) and whole[at][1] <= row[1]:
                at += 1
            if at == len(whole) or row[1] < whole[at][0] \
                    or row[1] + row[2] > whole[at][1]:
                continue              # outside a whole step
            names = scopes.get(whole[at][2], nothing)
            op_name = names["ops"].get(row[0])
            if op_name is None:
                unnamed += self_ns
            members = names["members"].get(row[0], ())
            opcode = names["bare"].get(row[0])
            if opcode is not None and not any(phase_of(m) for m in members):
                phase, bucket = None, "layout_copy"
                was = phase_of(op_name or "") or "unscoped"
                behind[was] = behind.get(was, 0) + self_ns
                opcodes[opcode] = opcodes.get(opcode, 0) + self_ns
            else:
                # what a bare fusion has under `ops` is its operand's name
                phase = phase_of((op_name or "") if opcode is None else "")
                if phase is None and members:
                    # a fusion the compiler named after a root of its own
                    # making (a copy, a transpose): its first scoped member
                    # speaks for it
                    op_name = next((m for m in members if phase_of(m)),
                                   op_name)
                    phase = phase_of(op_name or "")
                    by_members += self_ns if phase else 0
                bucket = phase or "unscoped"
            phase_ns[bucket] += self_ns
            spans = {GROUP[p] for p in
                     ({phase_of(m) for m in members} | {phase}) - {None}}
            if len(spans) > 1:
                pair = "+".join(sorted(spans))
                mixed[pair] = mixed.get(pair, 0) + self_ns
            block = block_of(op_name or "")
            if block is not None and phase in ("fwd", "bwd"):
                cell = blocks.setdefault(block, {"fwd": 0, "bwd": 0})
                cell[phase] += self_ns
            entry = ops.setdefault(row[0], [0, bucket, op_name])
            entry[0] += self_ns
    if not steps:
        return {"steps": 0}
    total = sum(phase_ns.values())

    def per_step(ns):
        return ns / steps / 1e6

    def ranked(keep):
        return [[name, per_step(ns), bucket, op_name] for
                name, (ns, bucket, op_name) in
                sorted(ops.items(), key=lambda kv: -kv[1][0]) if keep(bucket)]

    all_ops = ranked(lambda b: True)
    return {
        "steps": steps,
        "modules": sorted(modules),
        "phase_ms": {k: per_step(v) for k, v in phase_ns.items()},
        "busy_step_ms": per_step(total),
        "mixed_ms": per_step(sum(mixed.values())),
        "mixed_phase_pct": (100.0 * sum(mixed.values()) / total
                            if total else 0.0),
        "mixed_by_phases_ms": {k: per_step(v)
                               for k, v in sorted(mixed.items())},
        "named_pct": 100.0 * (1.0 - unnamed / total) if total else 0.0,
        "scoped_by_members_ms": per_step(by_members),
        "layout_copy_behind_ms": {k: per_step(v)
                                  for k, v in sorted(behind.items())},
        "layout_copy_opcodes_ms": {k: per_step(v)
                                   for k, v in sorted(opcodes.items())},
        "unscoped_ops": ranked(lambda b: b == "unscoped")[:12],
        "blocks": sorted(([k, per_step(v["fwd"]), per_step(v["bwd"])]
                          for k, v in blocks.items()),
                         key=lambda b: -(b[1] + b[2])),
        "top_ops": all_ops[:24],
        # every operation, for a reader that sums one scope of its own
        # (`harness/roofline.py`); not printed
        "ops": all_ops,
    }


# --- the run's newest trace, once a process ----------------------------------
_CACHE: dict[str, dict | None] = {}


def _newest_trace_dir(chip_dir: str) -> str | None:
    dirs = [d for d in glob.glob(os.path.join(chip_dir, "_work", "trace", "*"))
            if glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))]
    return max(dirs, key=os.path.getmtime) if dirs else None


def _names_state(scopes: dict) -> tuple[list[str], str]:
    """Which of the program's step scopes the modules' names hold, and
    whether they are this process's program's: the compile cache's key leaves
    names out, so an entry compiled by an older checkout serves ITS names to
    a program that has renamed its scopes ("stale": `tpudist.obs.scopes`
    declares `tpudist_loss`, the step has none)."""
    text = {n for names in scopes.values()
            for n in list(names["ops"].values())
            + [m for ms in names["members"].values() for m in ms]}
    seen = [s for s in ("tpudist_forward", "tpudist_loss",
                        "tpudist_grad_reduce", "tpudist_optimizer",
                        "tpudist_metrics") if any(s in n for n in text)]
    try:
        from tpudist.obs import scopes as declared
        stale = declared.LOSS not in seen
    except (ImportError, AttributeError):
        return seen, "older program"
    return seen, "stale compile-cache entry" if stale else "current"


def step_scopes(ctx: dict) -> dict | None:
    """`by_scope` of the newest trace under `_work/trace/` (one cell runs in
    one process), parsed once and shared by the readers; None as the module's
    docstring says. Prints one line `bench scope_ms {...}` (found or not, and
    why not) and writes `scopes.json` beside `summary.json`."""
    import time
    chip_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trace_dir = _newest_trace_dir(chip_dir)
    if trace_dir is None:
        return None
    path = trace_reduce.newest_xplane(trace_dir)
    if path in _CACHE:
        return _CACHE[path]
    t0 = time.perf_counter()
    peak = ctx["peak"]
    events = trace_reduce.extract(
        path, peak["trace_device_plane_prefix"],
        tuple(peak["trace_op_lines"]), tuple(peak["trace_module_lines"]))
    # the step is the module the window runs most: the busiest by time
    spent: dict[str, int] = {}
    for dev in events["devices"]:
        for row in dev["modules"]:
            name = row[0].split("(")[0]
            spent[name] = spent.get(name, 0) + row[2]
    module = max(spent, key=spent.get, default="")
    scopes = hlo_scopes(path, module) if module else None
    result = by_scope(events, scopes, module) if scopes else None
    why = None
    if scopes is None:
        why = "no Hlo Proto of the step module in the trace"
    elif not result["steps"]:
        why = "no whole step in the traced window"
    else:
        result["scopes_seen"], result["names"] = _names_state(scopes)
        unscoped = result["phase_ms"]["unscoped"]
        if "tpudist_forward" not in result["scopes_seen"]:
            why = "the step's names hold no tpudist_forward scope"
        elif unscoped > UNSCOPED_LIMIT * result["busy_step_ms"]:
            why = (f"{unscoped:.3f} ms of a {result['busy_step_ms']:.3f} ms "
                   f"step under no scope: {result['unscoped_ops'][:4]}")
    if why is not None:
        print("bench scope_ms " + json.dumps(
            {"found": False, "module": module, "why": why}), flush=True)
        _CACHE[path] = None
        return None
    # device idle a step that no host span overlaps: with every part of a
    # loop turn inside a tpudist.* span, what is left lies outside the loop
    trace = ctx.get("trace") or {}
    if trace.get("device_steps"):
        result["idle_unattributed_ms"] = 1e3 * dict(trace["idle_gaps"]).get(
            "host.unattributed", 0.0) / trace["device_steps"]
    result["module"] = module
    result["reader_s"] = time.perf_counter() - t0
    with open(os.path.join(trace_dir, "scopes.json"), "w") as f:
        json.dump(result, f)
    shown = dict(result, blocks=result["blocks"][:20],
                 top_ops=result["top_ops"][:12])
    del shown["ops"]
    print("bench scope_ms " + json.dumps(shown, default=float), flush=True)
    _CACHE[path] = result
    return result
