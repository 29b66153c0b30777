"""By hand, on the machine that took the trace: cut a profiler trace down to
the fixture `recorded_scopes.json.gz`, as `record_trace.py` cuts
`recorded_trace.json.gz`, with the program's names for the rows beside them.

    python benchmarks/chip/selftest/record_scopes.py <trace_dir> <out.json.gz> \
        [device_kind] [seconds]

Keeps the first `seconds` of the traced window (device op and module rows, the
host spans) and, of the scope map that `harness.scope_reduce.hlo_scopes` reads
from the same file's `/host:metadata` plane, the entries of the operations
those rows name. `test_scope_reduce.py` reduces the fixture with `by_scope`.
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main():
    from harness import scope_reduce, trace_reduce
    trace_dir, out = sys.argv[1], sys.argv[2]
    kind = sys.argv[3] if len(sys.argv) > 3 else "TPU v5 lite"
    seconds = float(sys.argv[4]) if len(sys.argv) > 4 else 0.5
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        peak = json.load(f)["device_kinds"][kind]
    path = trace_reduce.newest_xplane(trace_dir)
    ev = trace_reduce.extract(
        path, peak["trace_device_plane_prefix"], tuple(peak["trace_op_lines"]),
        tuple(peak["trace_module_lines"]))
    win = [r for r in ev["host"] if r[0] == trace_reduce.WINDOW_SPAN]
    lo = win[0][1] if win else min(r[1] for d in ev["devices"]
                                   for r in d["ops"])
    hi = lo + int(seconds * 1e9)

    def cut(rows):
        return [r for r in rows if r[1] >= lo and r[1] + r[2] <= hi]

    devices = [{"name": d["name"], "ops": cut(d["ops"]),
                "modules": cut(d["modules"])} for d in ev["devices"]]
    module = max((r[0] for d in devices for r in d["modules"]),
                 key=lambda n: sum(r[2] for d in devices for r in d["modules"]
                                   if r[0] == n)).split("(")[0]
    scopes = scope_reduce.hlo_scopes(path, module)
    named = {r[0] for d in devices for r in d["ops"]}
    kept = {r[0] for d in devices for r in d["modules"]}
    small = {"devices": devices,
             "host": [[trace_reduce.WINDOW_SPAN, lo, hi - lo]] + [
                 r for r in cut(ev["host"])
                 if r[0] != trace_reduce.WINDOW_SPAN],
             "module": module,
             "scopes": {full: {
                 "ops": {k: v for k, v in names["ops"].items() if k in named},
                 "members": {k: sorted(set(v)) for k, v in
                             names["members"].items() if k in named},
                 "bare": {k: v for k, v in names["bare"].items()
                          if k in named}}
                 for full, names in scopes.items() if full in kept}}
    with gzip.open(out, "wt") as f:
        json.dump(small, f, separators=(",", ":"))
    print("recorded", out, os.path.getsize(out), "bytes;",
          sum(len(d["ops"]) for d in devices), "op events;",
          sum(len(n["ops"]) for n in small["scopes"].values()),
          "named operations of", sorted(small["scopes"]))


if __name__ == "__main__":
    main()
