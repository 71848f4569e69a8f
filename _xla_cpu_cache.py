"""CPU-feature-keyed XLA persistent compile-cache location.

The XLA:CPU persistent cache stores AOT-compiled host kernels. Its entry key
covers the HLO and compile options but NOT the instruction set the host
compiler targeted — so a cache shared across machines (or across container
migrations of the same nodename) can serve kernels compiled with, say,
AVX-512 to a host without it, which dies with SIGILL/SIGSEGV at load. Keying
the directory by a hash of the actual CPU feature flags makes any
feature-set change land in a fresh cache instead of replaying stale code
(the r5/r6 slow-lane SIGSEGVs were this — nodename stayed stable across
hosts with different microarchitectures).

Standalone on purpose: tests/conftest.py must call this BEFORE ``import
jax``, so it cannot live under ``spark_rapids_tpu`` (whose package init
imports jax).
"""

from __future__ import annotations

import hashlib
import os
import platform
import tempfile


def cpu_feature_fingerprint() -> str:
    """Stable short hash of this host's CPU model + feature flags."""
    bits = [platform.machine()]
    model = ""
    flags: set = set()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 exposes "flags", arm64 "Features"
                if line.startswith(("flags", "Features")):
                    flags.update(line.split(":", 1)[1].split())
                elif line.startswith("model name") and not model:
                    model = line.split(":", 1)[1].strip()
    except OSError:
        model = platform.processor() or "unknown"
    bits.append(model)
    bits.append(" ".join(sorted(flags)))
    return hashlib.sha256("|".join(bits).encode()).hexdigest()[:16]


def cpu_cache_dir(tag: str = "srtpu_xla_cpu") -> str:
    return os.path.join(tempfile.gettempdir(),
                        f"{tag}_{cpu_feature_fingerprint()}")


def program_cache_dir() -> str:
    """Default location of the persistent jitted-program cache
    (exec/jit_persist.py). Same feature-hash scheme as the XLA:CPU kernel
    cache: the entry digest also folds the fingerprint in, so the
    directory keying is belt-and-braces against cross-host sharing."""
    return cpu_cache_dir("srtpu_jit_persist")
