#!/usr/bin/env bash
# Static-analysis smoke: the `pio lint` CI entry point (ISSUE 8).
#
# Three gates, mirroring what tier-1's tests/test_static_analysis.py
# asserts in-process:
#   1. `pio lint --json` over the whole repo exits 0 — zero findings
#      outside conf/lint_baseline.json (every baseline entry carries a
#      one-line justification; wildcards are rejected at load).
#   2. The JSON contract holds (ok=true, findings=[], stale baseline
#      entries empty — a fixed finding must be DELETED from the
#      baseline, not left to rot).
#   3. The run fits the <30 s tier-1 budget.
#
# Determinism: pure AST analysis — no storage, no jax import on the
# analysis path, no network; CPU env pinned anyway for uniformity with
# the other smokes.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0

report=$(mktemp /tmp/pio_lint_smoke.XXXXXX.json)
trap 'rm -f "$report"' EXIT

start=$(date +%s)
python -m predictionio_tpu.tools.cli lint --json > "$report"
elapsed=$(( $(date +%s) - start ))

cat "$report"

python - "$report" "$elapsed" <<'EOF'
import json, sys
with open(sys.argv[1]) as f:
    doc = json.load(f)
elapsed = int(sys.argv[2])
assert doc["ok"] is True, "pio lint reported findings outside the baseline"
assert doc["findings"] == [], doc["findings"]
assert doc["parseErrors"] == [], doc["parseErrors"]
assert doc["staleBaselineEntries"] == [], (
    "stale baseline entries — the findings were fixed, delete them: "
    + ", ".join(doc["staleBaselineEntries"]))
assert elapsed < 30, f"pio lint took {elapsed}s (budget 30s)"
print(f"lint smoke OK: {doc['files']} files, "
      f"{doc['suppressed']} baselined finding(s), {elapsed}s")
EOF
