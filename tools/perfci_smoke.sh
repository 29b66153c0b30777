#!/usr/bin/env bash
# Perf-console smoke: one command proves the unattended perf-CI chain on CPU.
#
#   1. the COMMITTED matrix (benchmarks/perfci.json) must validate and plan
#      under `tpudist-perfci --dry-run` — the arm-time check an
#      unattended run makes;
#   2. a tiny CPU matrix runs end to end: a row-producing stage appends to
#      a scratch history through regress.append_history, a platform-guarded
#      stage is skipped, the report/exit contract is 0;
#   3. a second run with a 30% slower row must trip the trailing-median
#      gate: exit 1 (findings), and a crashing stage must outrank it: 2;
#   4. `--dashboard` must render the self-contained trend artifact with the
#      regressed series flagged.
#
# Runs standalone (`bash tools/perfci_smoke.sh [workdir]`) and as the
# perfci-marked test tests/test_perfci.py::test_perfci_smoke_script.
# Prints PERFCI_SMOKE_OK as the last line on success.
set -euo pipefail
cd "$(dirname "$0")/.."

WORK="${1:-${TPUDIST_PERFCI_SMOKE_DIR:-$(mktemp -d)}}"
mkdir -p "$WORK"
HIST="$WORK/hist.jsonl"
REPORT="$WORK/perfci_report.json"
MANIFEST="$WORK/manifest.json"

echo "[perfci-smoke] 1/4 committed manifest validates" >&2
python -m tpudist.perfci --dry-run --platform cpu >/dev/null

cat > "$MANIFEST" <<'JSON'
{
  "stages": [
    {"name": "rows",
     "cmd": ["python", "-c",
             "import json, os; print(json.dumps({'metric': 'smoke_ips', 'value': float(os.environ['SMOKE_VAL']), 'unit': 'images/sec'}))"],
     "append_stdout_rows": true, "series": ["smoke_ips"], "timeout_s": 120},
    {"name": "chip_only",
     "cmd": ["python", "-c", "raise SystemExit('must never run on cpu')"],
     "platforms": ["tpu"], "timeout_s": 60}
  ]
}
JSON

echo "[perfci-smoke] 2/4 clean matrix run (scratch history)" >&2
SMOKE_VAL=1000 python -m tpudist.perfci --manifest "$MANIFEST" \
    --history "$HIST" --report "$REPORT" --platform cpu
python - "$REPORT" <<'PY'
import json, sys
rep = json.load(open(sys.argv[1]))
s = rep["summary"]
assert rep["exit"] == 0 and s["stages_ok"] == 1 and s["stages_skipped"] == 1
assert s["rows_appended"] == 1, s
by = {st["name"]: st["status"] for st in rep["stages"]}
assert by == {"rows": "ok", "chip_only": "skipped_platform"}, by
print("[perfci-smoke] report ok", file=sys.stderr)
PY

echo "[perfci-smoke] 3/4 gate + exit contract" >&2
# arm the baseline, then a 30% slower row must exit 1
SMOKE_VAL=1010 python -m tpudist.perfci --manifest "$MANIFEST" \
    --history "$HIST" --report "$REPORT" --platform cpu
set +e
SMOKE_VAL=700 python -m tpudist.perfci --manifest "$MANIFEST" \
    --history "$HIST" --report "$REPORT" --platform cpu \
    --dashboard "$WORK/dashboard.html"
rc=$?
set -e
if [[ "$rc" != 1 ]]; then
    echo "[perfci-smoke] expected exit 1 on a 30% regression, got $rc" >&2
    exit 1
fi
# an operationally failed stage outranks the finding: exit 2
cat > "$WORK/crash.json" <<'JSON'
{"stages": [{"name": "dies",
             "cmd": ["python", "-c", "import sys; sys.exit(3)"],
             "timeout_s": 60}]}
JSON
set +e
python -m tpudist.perfci --manifest "$WORK/crash.json" --history "$HIST" \
    --report "$WORK/crash_report.json" --platform cpu
rc=$?
set -e
if [[ "$rc" != 2 ]]; then
    echo "[perfci-smoke] expected exit 2 on a crashed stage, got $rc" >&2
    exit 1
fi

echo "[perfci-smoke] 4/4 dashboard artifact" >&2
python - "$WORK/dashboard.html" <<'PY'
import os, sys
doc = open(sys.argv[1], encoding="utf-8").read()
assert os.path.getsize(sys.argv[1]) > 0
assert 'data-metric="smoke_ips"' in doc and 'data-status="regression"' in doc
assert "<script" not in doc.lower(), "dashboard must stay zero-dependency"
print(f"[perfci-smoke] dashboard ok ({len(doc)} bytes)", file=sys.stderr)
PY

echo "PERFCI_SMOKE_OK"
