#!/usr/bin/env bash
# Tier-1 verify wrapper — the ROADMAP.md command, runnable as one step:
#
#     tools/run_tier1.sh [--trace DIR]
#
# CPU-only (8 virtual devices via tests/conftest.py), slow-marked tests
# excluded, 2400 s hard timeout (raised 870 -> 1500 in PR 3, 1500 ->
# 2400 in PR 17 — the suite has grown to 782 tests and measures
# ~1750 s wall quiet; a killed run ends mid-dots with no summary
# line).  --durations=15 prints the slowest tests as the run
# goes green, so a timeout-killed log (ends mid-dots) is diagnosable
# from the previous run's report instead of guesswork.  Prints
# DOTS_PASSED=<n> (the driver's pass-count metric) and exits with
# pytest's return code.
#
# --trace DIR exports the run's apex_tpu.obs telemetry (every
# instrumented engine/driver span the suite exercised) into DIR as
# trace.jsonl / trace.chrome.json / metrics.json at session end
# (tests/conftest.py hook); render it with
#     python tools/trace_report.py DIR
set -o pipefail
cd "$(dirname "$0")/.."
TRACE_DIR=""
while [[ $# -gt 0 ]]; do
    case "$1" in
        --trace)
            TRACE_DIR="$2"; shift 2 ;;
        --trace=*)
            TRACE_DIR="${1#--trace=}"; shift ;;
        *)
            echo "unknown argument: $1 (usage: run_tier1.sh [--trace DIR])" >&2
            exit 2 ;;
    esac
done
LOG="${TIER1_LOG:-/tmp/_t1.log}"
rm -f "$LOG"
timeout -k 10 2400 env JAX_PLATFORMS=cpu \
    ${TRACE_DIR:+APEX_TPU_OBS_TRACE_DIR="$TRACE_DIR"} \
    python -m pytest tests/ -q -m 'not slow' \
    --durations=15 \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)
# Timeout detection (ISSUE 8): a timeout-killed run is rc=124 (137 if
# the KILL followup fired) and its log ends mid-progress-dots with no
# "=== ... ===" summary line — the exact signature ROADMAP.md warns
# about.  Make it explicit instead of leaving a silently truncated log
# that reads like a test failure.
if [[ $rc -eq 124 || $rc -eq 137 ]] || {
    [[ $rc -ne 0 ]] && ! grep -qaE '^=+ .* =+$' "$LOG"; }; then
    last=$(grep -av '^[[:space:]]*$' "$LOG" | tail -n 1)
    if [[ $rc -eq 124 || $rc -eq 137 || "$last" =~ ^[.FEsx]+([[:space:]]*\[[[:space:]]*[0-9]+%\])?$ ]]; then
        echo "TIER1_TIMEOUT: run killed by the 2400s timeout (rc=$rc);" \
             "log ends mid-progress-dots with no pytest summary —" \
             "this is a TIMEOUT, not a test failure. See the last" \
             "--durations report in a complete run for the slow tests."
    fi
fi
if [[ -n "$TRACE_DIR" && -f "$TRACE_DIR/trace.jsonl" ]]; then
    echo "TRACE_ARTIFACT=$TRACE_DIR/trace.jsonl"
fi
# apexlint banner (ISSUE 19): the one-line census of the AST invariant
# sweep (tools/apexlint.py is jax-free and ~2 s; --summary always
# exits 0, so the tier-1 rc is untouched — the hard gate is the
# apexlint lint_graphs check and tests/test_staticcheck.py).
python tools/apexlint.py --summary 2>/dev/null || true
exit $rc
