#!/bin/sh
# The tests still behind SRTPU_SLOW_LANE: the modules of tests/conftest.py's
# SLOW_LANE_MODULES and test_pipeline.py's tracker-wide prefetch
# differential (ROADMAP D12 says why each is not in tier-1 yet). Everything
# else under tests/ runs in the tier-1 command.
set -e
cd "$(dirname "$0")/.."
SRTPU_SLOW_LANE=1 exec python -m pytest \
    tests/test_distributed.py tests/test_autotune_warm.py \
    tests/test_pipeline.py -q "$@"
