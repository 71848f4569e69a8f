"""Test harness: virtual 8-device CPU mesh by default; real-TPU lane opt-in.

Mirrors the reference's approach of testing distributed machinery without a
cluster (SURVEY.md section 4): jax is forced onto the host platform with 8
virtual devices so sharding/shuffle tests exercise real collectives.

``SRTPU_TPU_LANE=1`` runs on the real chip instead (the reference's "real
GPU required, no fake backend" discipline for its retry/kernel suites —
SURVEY.md section 4): no platform override, single device. Multi-device
tests must skip there (the ``cpu_mesh`` fixture below). Run via
``tests/run_tpu_lane.sh``.
"""

import os
import sys

TPU_LANE = os.environ.get("SRTPU_TPU_LANE") == "1"

if not TPU_LANE:
    # Must happen before jax initializes a backend.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    # CPU lanes use a compile cache keyed by the host's CPU FEATURE SET,
    # not its nodename: a nodename-keyed cache survives container moves
    # across different microarchitectures, and AOT kernels compiled under
    # other feature flags SIGILL/SIGSEGV when loaded here
    # (_xla_cpu_cache.py; the r5/r6 slow-lane segfaults were this)
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if _ROOT not in sys.path:
        sys.path.insert(0, _ROOT)
    from _xla_cpu_cache import cpu_cache_dir
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cpu_cache_dir())

# Hermetic autotune store: without this, the in-process suite would read
# and write the host-shared default timing store, making dispatch (and any
# differential assertion) depend on what ran on this machine before.
import tempfile  # noqa: E402

os.environ["SRTPU_AUTOTUNE_DIR"] = tempfile.mkdtemp(
    prefix="srtpu_autotune_test_")

import jax  # noqa: E402

if not TPU_LANE:
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


# Slow opt-in lane (VERDICT r4 weak #6: a suite nobody can afford to run
# stops being run): the multi-process/differential suites below take many
# minutes each and run via tests/run_slow_lane.sh (SRTPU_SLOW_LANE=1) —
# the default lane stays fast. CI/driver should run both.
SLOW_LANE_MODULES = ("test_distributed", "test_cluster", "test_tpcds",
                     "test_scaletest", "test_fusion_diff", "test_reuse_diff",
                     "test_warmstart", "test_autotune_warm")
SLOW_LANE = os.environ.get("SRTPU_SLOW_LANE") == "1"


def pytest_collection_modifyitems(config, items):
    if not SLOW_LANE:
        skip_slow = pytest.mark.skip(
            reason="slow differential lane; run tests/run_slow_lane.sh")
        for item in items:
            mod = item.nodeid.split("::")[0].rsplit("/", 1)[-1]
            if mod.removesuffix(".py") in SLOW_LANE_MODULES:
                item.add_marker(skip_slow)
    if not TPU_LANE:
        return
    skip_multi = pytest.mark.skip(
        reason="needs the 8-device CPU mesh; TPU lane has one real chip")
    for item in items:
        if "test_parallel" in item.nodeid:
            item.add_marker(skip_multi)


def pytest_sessionfinish(session, exitstatus):
    # MemoryCleaner-style end-of-suite sweep (reference: Plugin.scala:575-590
    # shutdown leak check): pool balances must return to zero and no spill
    # files may outlive their frameworks. Reported as a hard error so leaks
    # cannot land silently.
    if exitstatus != 0:
        return  # don't mask real failures with leak noise
    try:
        from spark_rapids_tpu.mem import cleaner
    except Exception:
        return
    try:
        # tests that drive physical_plan() directly never run the DataFrame
        # cleanup walk — drop any reuse-cache entries they left pinned
        # before the pool-balance sweep below
        from spark_rapids_tpu.exec import reuse
        reuse.release_stragglers()
    except Exception:
        pass
    leaks = [l for l in cleaner.sweep()
             if "HbmPool" in l or "orphan spill file" in l]
    if leaks:
        raise RuntimeError("end-of-suite leak sweep:\n" + "\n".join(leaks))
