"""By hand, on the machine that took the trace: cut a profiler trace down to a
fixture small enough to keep (`recorded_trace.json.gz`).

    python benchmarks/chip/selftest/record_trace.py <trace_dir> <out.json.gz> \
        [device_kind] [seconds]

Keeps the first `seconds` of the traced window: the device planes' op and
module events and the host annotation spans, as `harness.trace_reduce.extract`
gives them. `test_trace_reduce.py` reduces the fixture and checks the numbers
against sums worked out by hand from the same rows.
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main():
    from harness import trace_reduce
    trace_dir, out = sys.argv[1], sys.argv[2]
    kind = sys.argv[3] if len(sys.argv) > 3 else "TPU v5 lite"
    seconds = float(sys.argv[4]) if len(sys.argv) > 4 else 0.5
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        peak = json.load(f)["device_kinds"][kind]
    ev = trace_reduce.extract(
        trace_reduce.newest_xplane(trace_dir),
        peak["trace_device_plane_prefix"], tuple(peak["trace_op_lines"]),
        tuple(peak["trace_module_lines"]))
    win = [r for r in ev["host"] if r[0] == trace_reduce.WINDOW_SPAN]
    lo = win[0][1] if win else min(r[1] for d in ev["devices"]
                                   for r in d["ops"])
    hi = lo + int(seconds * 1e9)

    def cut(rows):
        return [r for r in rows if r[1] >= lo and r[1] + r[2] <= hi]

    small = {"devices": [{"name": d["name"], "ops": cut(d["ops"]),
                          "modules": cut(d["modules"])}
                         for d in ev["devices"]],
             "host": [[trace_reduce.WINDOW_SPAN, lo, hi - lo]] + [
                 r for r in cut(ev["host"])
                 if r[0] != trace_reduce.WINDOW_SPAN],
             "seen": ev["seen"]}
    with gzip.open(out, "wt") as f:
        json.dump(small, f, separators=(",", ":"))
    print("recorded", out, os.path.getsize(out), "bytes;",
          sum(len(d["ops"]) for d in small["devices"]), "op events")


if __name__ == "__main__":
    main()
