#!/bin/sh
# Chaos lane: every tracker TPC-H/TPC-DS query runs under a seeded fault
# schedule (injected OOMs, corrupted shuffle blocks, slow serializes,
# dropped fetches) and must be bit-identical to the fault-free run with
# srtpu_fault_recovered_total > 0 — the acceptance net for the hardened
# retry/refetch/degradation paths (docs/fault_injection.md). The executor
# kill + recompute paths run in the cluster suite (tests/test_cluster.py).
# tests/test_serve.py adds the concurrent-serving variant: N client threads
# through the QueryServer under seeded serve.admit/serve.cancel faults,
# still bit-identical to the fault-free serial run (docs/serving.md).
#
# SRTPU_FAULTS_SEED pins the schedule so failures reproduce exactly.
set -e
cd "$(dirname "$0")/.."
rc=0
SRTPU_CHAOS_LANE=1 SRTPU_FAULTS_SEED="${SRTPU_FAULTS_SEED:-42}" \
    python -m pytest tests/test_faults.py tests/test_reuse.py \
    tests/test_serve.py -q "$@" || rc=$?
if [ "$rc" -ne 0 ]; then
    # keep the evidence: dump the journal/metrics/trace state the failing
    # run left behind as a diagnostics bundle (tools/obs_report.py)
    OBS_FAIL_OUT="${TMPDIR:-/tmp}/srtpu_chaos_failure_report"
    echo "chaos lane failed (rc=$rc): dumping diagnostics bundle to" \
         "$OBS_FAIL_OUT" >&2
    python tools/obs_report.py --out "$OBS_FAIL_OUT" >&2 || true
fi
exit $rc
