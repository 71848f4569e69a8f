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


# Every executable XLA:CPU loads is many memory mappings, a process may
# hold 65,530 (``vm.max_map_count``), and the load that passes the limit
# segfaults inside the compiler and takes its xdist worker down (ROADMAP
# D1). A worker that has run a few hundred tests holds tens of thousands,
# and the heaviest modules add 28,000-46,000 of their own (test_warmstart,
# test_fusion_diff, test_agg_window), so a worker starts a module with at
# most a fifth of the limit in use.
MAPPINGS_CROWDED = 12_000


def mappings() -> int:
    with open("/proc/self/maps") as f:
        return sum(1 for _ in f)


def drop_programs() -> None:
    """Give back the mappings of the programs this process has loaded;
    what is needed again is loaded again from the compile cache."""
    import gc

    from spark_rapids_tpu.exec import jit_cache
    jit_cache._CACHE.clear()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module", autouse=True)
def _room_for_programs():
    if mappings() > MAPPINGS_CROWDED:
        drop_programs()


# Modules still behind SRTPU_SLOW_LANE=1 (tests/run_slow_lane.sh), which
# nothing runs: ROADMAP D12 has each one's time and what would bring it in.
SLOW_LANE_MODULES = ("test_distributed", "test_autotune_warm")
SLOW_LANE = os.environ.get("SRTPU_SLOW_LANE") == "1"


def pytest_collection_modifyitems(config, items):
    if not SLOW_LANE:
        skip_slow = pytest.mark.skip(
            reason="behind SRTPU_SLOW_LANE; run tests/run_slow_lane.sh")
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
