#!/bin/sh
# Real-TPU differential lane: the expression/operator/string/window/TPC-H
# subset of the suite on the actual chip (no CPU-mesh override), the way the
# reference runs its kernel/retry suites on a real GPU (SURVEY.md section 4).
# First run pays per-kernel compiles; the persistent XLA cache
# (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) makes reruns fast.
set -e
cd "$(dirname "$0")/.."
SRTPU_TPU_LANE=1 exec python -m pytest \
    tests/test_exprs.py tests/test_exec.py tests/test_strings.py \
    tests/test_window.py tests/test_tpch.py tests/test_dict.py \
    tests/test_columnar.py -q "$@"
